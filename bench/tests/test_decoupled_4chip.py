"""`ch_w2_decoupled_4chip.adhoc` on the CPU with four forced host devices:
the configuration's own `replica_devices` places one replica on each
device through the program's option, the kernel warm-up compiles on every
device, no program is built in the measured window, and the run is
`correct`, and every per-layer metric the cell lists that the program
feeds (all but the device trace's) reads a number.  And the reader of the
placement layer, on synthetic inputs.

The device count has to be set before JAX starts, so the run goes in a
subprocess (`run_tiny`), at the tiny schema of `conftest.tiny_config`."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
CELL = "ch_w2_decoupled_4chip.adhoc"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())

RUN = r'''
import io, json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import bench.harness, bench.run
from bench.tests.conftest import tiny_config

bench.harness.load_config = tiny_config
bench.run.configure_compile_cache = lambda: "off"
args = bench.run.parse_args(["--workload", sys.argv[2], "--seed",
                             str(2**31 + 977), "--seconds", "1",
                             "--trace", "1"])
out, err = io.StringIO(), io.StringIO()
rc = bench.run.run_cell(args, require_tpu=False, out=out, err=err)
print(json.dumps({"rc": rc, "err": err.getvalue(), "out": out.getvalue()}))
'''


def run_tiny(cell: str, devices: int) -> tuple[dict, dict]:
    """One traced run of `cell` at the tiny schema on `devices` forced CPU
    devices: run.py's stderr and rc, and its result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    proc = subprocess.run([sys.executable, "-c", RUN, str(ROOT), cell],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    run = json.loads(proc.stdout.strip().splitlines()[-1])
    assert run["rc"] == 0, run["err"][-4000:]
    return run, json.loads(run["out"].strip().splitlines()[-1])


def program_metrics(cell: str) -> list[str]:
    """The per-layer metrics `cell` lists that the program or the harness
    feeds: all but those read from the device trace, which a CPU run
    lacks."""
    return [m["name"] for m in BENCH["per_layer"]
            if cell in m["workloads"] and m["source"] != "device_trace"]


@pytest.fixture(scope="module")
def four_chip_run():
    return run_tiny(CELL, 4)


def test_the_placed_run_is_correct(four_chip_run):
    _run, result = four_chip_run
    assert result["correct"] is True, result["checks"]
    assert result["checks"]["mismatched_results"]["value"] == 0
    assert result["device"]["count"] == 4
    assert len(result["device"]["memory_peak_by_chip"]) == 4


@pytest.mark.parametrize("metric", program_metrics(CELL))
def test_every_listed_program_metric_reads_a_number(four_chip_run, metric):
    _run, result = four_chip_run
    assert isinstance(result["metrics"][metric]["value"], (int, float))


def test_nothing_is_built_in_the_window(four_chip_run):
    run, _result = four_chip_run
    assert "inside it 0 compiles and 0 cache loads" in run["err"]


def test_the_warm_up_compiles_on_every_device(four_chip_run):
    run, _result = four_chip_run
    line = next(ln for ln in run["err"].splitlines()
                if ln.startswith("kernel warm-up:"))
    per_chip = {c: int(n) for n, c in re.findall(r"(\d+) on (cpu:\d)", line)}
    assert sorted(per_chip) == [f"cpu:{i}" for i in range(4)], line
    assert min(per_chip.values()) > 0, line
    # the program serves under each replica's default device, so the
    # scratch mirror serves under each
    assert "scratch serves under default device cpu:0, cpu:1, cpu:2, " \
        "cpu:3" in line


def _input(*, busy=None):
    trace = None if busy is None else type("Trace", (), {
        "busy_by_chip": busy})()
    return harness.LayerInput(
        window=harness.Window(t0=0.0, t1=30.0), spans={}, totals={},
        stages={}, trace=trace, peaks={})


@pytest.mark.parametrize("busy, expected", [
    ({"/device:TPU:0": 2.0, "/device:TPU:1": 1.5, "/device:TPU:2": 1.0,
      "/device:TPU:3": 1.6}, 50.0),
    ({"/device:TPU:0": 3.0, "/device:TPU:1": 3.0}, 100.0),
    (None, None),                                   # no trace
    ({"/device:TPU:0": 2.0}, None),                 # one chip
    ({"/device:TPU:0": 0.0, "/device:TPU:1": 0.0}, None),   # idle chips
])
def test_chip_busy_balance(busy, expected):
    value = harness.load_reader("chip_busy_balance")(_input(busy=busy))
    assert value == (None if expected is None else pytest.approx(expected))
