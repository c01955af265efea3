"""The trace reduction on a small trace recorded on one TPU v5e
(`record_trace.py`): inside a `bench:window` annotation, the fused scan
kernel ran twice under `bench:flush` annotations, with an unannotated
20 ms host sleep between them."""

from __future__ import annotations

from pathlib import Path

import pytest

from bench.cost import is_scan_kernel
from bench.trace_reduce import op_label, reduce_trace

TRACE = Path(__file__).resolve().parent / "data" / "scan_window.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return reduce_trace(str(TRACE))


def test_window_busy_and_idle(summary):
    assert summary.n_devices == 1
    assert summary.window_s == pytest.approx(0.02335364, rel=1e-6)
    assert 0 < summary.busy_s < 0.001
    assert summary.idle_share == pytest.approx(
        1 - summary.busy_s / summary.window_s)
    assert sum(summary.idle_by_label.values()) == pytest.approx(
        summary.window_s - summary.busy_s)


def test_scan_kernels_are_found_by_name(summary):
    assert "rss_scan_agg_grouped:custom-call" in summary.op_seconds
    kernel_s = summary.seconds_where(is_scan_kernel)
    assert 0 < kernel_s <= summary.busy_s
    assert not is_scan_kernel("rss_delta_fold:custom-call")
    assert not is_scan_kernel("copy:copy")


def test_the_longest_gap_is_the_unannotated_sleep(summary):
    label, seconds = summary.idle_gaps[0]
    assert label == "other"
    assert 0.015 < seconds < summary.window_s
    assert [g[1] for g in summary.idle_gaps] == sorted(
        (g[1] for g in summary.idle_gaps), reverse=True)


def test_op_label():
    assert op_label("%rss_scan_agg_grouped.1 = s32[8,128]{1,0} custom-call("
                    "s32[1,128] %a), custom_call_target=\"tpu_custom_call\"") \
        == "rss_scan_agg_grouped:custom-call"
    assert op_label("%copy.5 = s32[8]{0} copy(s32[8]{0} %x)") == "copy:copy"
    assert op_label("jit_rss_scan_agg(123)") == "jit_rss_scan_agg(123)"


def test_a_trace_without_the_window_annotation_is_refused(tmp_path):
    with pytest.raises(ValueError):
        reduce_trace(str(TRACE), prefix="nothing:")
