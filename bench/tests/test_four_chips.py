"""A rehearsal, on the CPU with four forced host devices, of a cell whose
replicas sit on four chips: the kernel warm-up compiles on every chip, so
no program is built in the measured window; replays on chip 0 alone leave
another chip's shapes to be built when the window meets them.

The program places nothing on a device of its own yet, so the test stands
in for placement: replica i is built and serves (WAL replay, plan
execution, version GC) under `jax.default_device(jax.devices()[i])`.  The
device count has to be set before JAX starts, so the rehearsal runs in a
subprocess."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

REHEARSAL = r'''
import io, json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax
import jax.numpy as jnp
import numpy as np
import bench.harness, bench.run
from bench.harness import CompileClock
from bench.kernel_warmup import KernelWarmup
from bench.tests.conftest import tiny_config
from repro.mvcc import htap

devs = jax.devices()
built = [0]
real_init = htap.Replica.__init__

def init(self, *a, **k):
    self._chip = devs[built[0] % len(devs)]
    built[0] += 1
    with jax.default_device(self._chip):
        real_init(self, *a, **k)

def on_chip(fn):
    def served(self, *a, **k):
        with jax.default_device(self._chip):
            return fn(self, *a, **k)
    return served

htap.Replica.__init__ = init
for name in ("catch_up", "_execute", "gc_versions"):
    setattr(htap.Replica, name, on_chip(getattr(htap.Replica, name)))

cfg = dict(tiny_config("ch_w2_decoupled"), replicas=4,
           route_policy="round_robin")
bench_json = json.loads(open(sys.argv[1] + "/BENCHMARK.json").read())
cell = {"name": "ch_w2_decoupled.adhoc", "config": "ch_w2_decoupled",
        "traffic": "adhoc", "chips": 4}
bench.run.find_cell = lambda name: (bench_json, cell)
bench.harness.load_config = lambda name: cfg
bench.run.configure_compile_cache = lambda: "off"
args = bench.run.parse_args(["--workload", cell["name"], "--seed",
                             str(2**31 + 41), "--seconds", "1",
                             "--trace", "0"])
out, err = io.StringIO(), io.StringIO()
rc = bench.run.run_cell(args, require_tpu=False, out=out, err=err)
print(json.dumps({"run": {"rc": rc, "err": err.getvalue(),
                          "out": out.getvalue()}}))


def replayed_then_served_on_chip_2(chips, pages):
    """Record one scan served on chip 0 with a 2-long member array, replay
    on `chips`, then serve the same scan on chip 2 with a member array of
    a length the replay reached; the programs that serve built."""
    from repro.kernels.rss_scan_agg import ops
    warm = KernelWarmup(chips)

    def store():
        return (jnp.zeros((pages, 8, 32), jnp.int32),
                jnp.zeros((pages, 8), jnp.int32))

    def serve(data, ts, members):
        return ops.rss_scan_agg(data, ts, np.zeros(members, np.int32), 0,
                                1, -2, 5, block_pages=8)

    with jax.default_device(devs[0]):
        jax.block_until_ready(serve(*store(), 2))
    n = warm.replay()
    with jax.default_device(devs[2]):
        data, ts = jax.block_until_ready(store())
        clock = CompileClock()
        jax.block_until_ready(serve(data, ts, 5))
        clock.close()
    return {"calls": n, "per_chip": warm.per_chip,
            "compiles": clock.compiles, "names": clock.names}


print(json.dumps({"chip0": replayed_then_served_on_chip_2(devs[:1], 16),
                  "all": replayed_then_served_on_chip_2(devs, 24)}))
'''


def test_four_chip_decoupled_run_builds_nothing_in_the_window():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    proc = subprocess.run([sys.executable, "-c", REHEARSAL, str(ROOT)],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    run, warm = lines[0]["run"], lines[1]

    # the whole run: every chip warmed alike, nothing built in the window
    assert run["rc"] == 0, run["err"][-4000:]
    result = json.loads(run["out"].strip().splitlines()[-1])
    assert result["correct"] is True
    assert result["device"]["count"] == 4
    assert len(result["device"]["memory_peak_by_chip"]) == 4
    assert "inside it 0 compiles and 0 cache loads" in run["err"]
    line = next(ln for ln in run["err"].splitlines()
                if ln.startswith("kernel warm-up:"))
    per_chip = {c: int(n) for n, c in re.findall(r"(\d+) on (cpu:\d)", line)}
    assert sorted(per_chip) == [f"cpu:{i}" for i in range(4)], line
    assert per_chip["cpu:0"] > 0 and len(set(per_chip.values())) == 1
    assert "scratch serves under default device cpu:0, cpu:1, cpu:2, " \
        "cpu:3" in line

    # replays on chip 0 alone: a length they reached is built on chip 2
    assert warm["chip0"]["per_chip"] == {"cpu:0": 9}
    assert warm["chip0"]["compiles"] >= 1
    assert any("rss_scan_agg" in n for n in warm["chip0"]["names"])
    # replays on every chip: chip 2 finds the program built
    assert warm["all"]["per_chip"] == {f"cpu:{i}": 9 for i in range(4)}
    assert warm["all"]["compiles"] == 0, warm["all"]["names"]
