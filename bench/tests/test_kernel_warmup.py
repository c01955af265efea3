"""The kernel warm-up: the member-array lengths it replays come from the
traffic it recorded, a call it cannot replay stops the run, and a run in
whose window a program is built prints no result."""

from __future__ import annotations

import io

import numpy as np
import pytest

from bench import kernel_warmup, run as bench_run
from bench.kernel_warmup import KernelWarmup, WarmupError


def _fake_kernels(monkeypatch, calls: list, fail_above: int | None = None):
    """Stand-ins of the jitted kernels with the program's argument names,
    recording each call's varying length."""
    from repro.kernels.rss_scan_agg import ops

    def scan(data, ts, member_ts, floor=0, *, interpret=None):
        if fail_above is not None and len(member_ts) > fail_above:
            raise TypeError("no such shape")
        calls.append(("scan", len(member_ts)))
        return np.zeros(1)

    def fold(acc, delta, *, interpret=None):
        calls.append(("fold", acc.shape, delta.shape[0]))
        return acc
    for name in kernel_warmup.SCAN_KERNELS:
        monkeypatch.setattr(ops, name, scan)
    monkeypatch.setattr(ops, kernel_warmup.FOLD_KERNEL, fold)
    return ops


def test_replay_reaches_twice_the_longest_member_array_seen(monkeypatch):
    calls: list = []
    ops = _fake_kernels(monkeypatch, calls)
    warm = KernelWarmup()
    ops.rss_scan_agg(np.zeros(4), np.zeros(4), np.zeros(5, np.int32))
    ops.rss_scan_agg(np.zeros(4), np.zeros(4), np.zeros(3, np.int32))
    ops.rss_delta_fold(np.zeros((8, 128), np.int32),
                       np.zeros((16, 128), np.int32))
    assert warm.longest == 5 and warm.members_to() == 14
    calls.clear()
    n = warm.replay()
    scans = sorted(m for kind, m, *_ in calls if kind == "scan")
    assert scans == list(range(15))
    folds = [c[2] for c in calls if c[0] == "fold"]
    assert folds == [8, 16, 32, 64, 128, 256]
    assert n == 15 + 6
    assert ops.rss_scan_agg is warm.real["rss_scan_agg"]


def test_one_chip_replays_the_calls_as_recorded(monkeypatch):
    """With the cell's one chip, each call is replayed as the program made
    it, in the same number: on the recorded arrays themselves, with no
    default device set, inline."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.rss_scan_agg import ops
    seen: list = []

    def scan(data, ts, member_ts, floor=0, *, interpret=None):
        seen.append(("scan", data, type(member_ts), len(member_ts),
                     jax.config.jax_default_device))
        return np.zeros(1)

    def fold(acc, delta, *, interpret=None):
        seen.append(("fold", acc, type(delta), delta.shape[0],
                     jax.config.jax_default_device))
        return acc
    for name in kernel_warmup.SCAN_KERNELS:
        monkeypatch.setattr(ops, name, scan)
    monkeypatch.setattr(ops, kernel_warmup.FOLD_KERNEL, fold)
    data = jnp.zeros((8, 4), jnp.int32)
    acc = jnp.zeros((8, 128), jnp.int32)
    warm = KernelWarmup(jax.local_devices()[:1])
    ops.rss_scan_agg(data, jnp.zeros(8), np.zeros(5, np.int32))
    ops.rss_delta_fold(acc, jnp.zeros((16, 128), jnp.int32))
    seen.clear()
    assert warm.replay() == 15 + 6
    assert warm.per_chip == {f"{jax.local_devices()[0].platform}:"
                             f"{jax.local_devices()[0].id}": 21}
    scans = [c for c in seen if c[0] == "scan"]
    assert [c[3] for c in scans] == list(range(15))
    assert all(c[1] is data and c[2] is np.ndarray for c in scans)
    folds = [c for c in seen if c[0] == "fold"]
    assert [c[3] for c in folds] == [8, 16, 32, 64, 128, 256]
    assert all(c[1] is acc and issubclass(c[2], jax.Array) for c in folds)
    assert {c[4] for c in seen} == {None}


def test_a_call_that_cannot_be_replayed_raises(monkeypatch):
    ops = _fake_kernels(monkeypatch, [], fail_above=6)
    warm = KernelWarmup()
    ops.rss_scan_agg(np.zeros(4), np.zeros(4), np.zeros(2, np.int32))
    with pytest.raises(WarmupError, match="rss_scan_agg"):
        warm.replay()


def test_a_kernel_missing_from_the_program_raises(monkeypatch):
    from repro.kernels.rss_scan_agg import ops
    monkeypatch.delattr(ops, kernel_warmup.FOLD_KERNEL)
    with pytest.raises(WarmupError, match="rss_delta_fold"):
        KernelWarmup()


def _run(workload: str = "ch_w2_unified.adhoc") -> tuple[int, str, str]:
    args = bench_run.parse_args(["--workload", workload, "--seed",
                                 str(2**31 + 21), "--seconds", "0.5",
                                 "--trace", "0"])
    out, err = io.StringIO(), io.StringIO()
    rc = bench_run.run_cell(args, require_tpu=False, out=out, err=err)
    return rc, out.getvalue(), err.getvalue()


def test_a_program_built_in_the_window_stops_the_run(tiny_cells,
                                                     monkeypatch):
    import bench.harness
    close = bench.harness.CompileClock.close

    def close_after_a_compile(self):
        self.compiles, self.names = 1, ["jit(rss_scan_agg)"]
        close(self)
    monkeypatch.setattr(bench.harness.CompileClock, "close",
                        close_after_a_compile)
    rc, out, err = _run()
    assert rc == 1 and out == ""
    assert "jit(rss_scan_agg)" in err


def test_a_failed_warm_up_stops_the_run(tiny_cells, monkeypatch):
    def replay(self):
        raise WarmupError("rss_scan_agg cannot be replayed")
    monkeypatch.setattr(KernelWarmup, "replay", replay)
    rc, out, err = _run()
    assert rc == 1 and out == ""
    assert "kernel warm-up failed" in err


def test_the_compile_clock_counts_every_compile_of_the_warm_up_threads():
    """The warm-up compiles on a thread per chip; no count is lost."""
    import sys
    import threading

    from bench.harness import CompileClock
    clock = CompileClock()
    clock.close()
    per_thread, n_threads = 2000, 16
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def compile_events():
            for _ in range(per_thread):
                clock._on_dur(CompileClock._COMPILE, 0.001,
                              fun_name="jit(rss_scan_agg)")
                clock._on_event(CompileClock._CACHE_HIT)
        threads = [threading.Thread(target=compile_events)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert clock.compiles == clock.cache_loads == per_thread * n_threads
    assert len(clock.names) == per_thread * n_threads
