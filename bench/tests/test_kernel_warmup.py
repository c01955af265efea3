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
