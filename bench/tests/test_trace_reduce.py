"""The trace reduction on a small trace recorded on one TPU v5e
(`record_trace.py`): inside a `bench:window` annotation, the fused scan
kernel ran twice under `bench:flush` annotations, with an unannotated
20 ms host sleep between them; and on a made-up trace of two chips with
program spans."""

from __future__ import annotations

import types
from pathlib import Path

import pytest

from bench.cost import is_scan_kernel
from bench.trace_reduce import _innermost, op_label, reduce_trace

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
    assert label == "other/-"
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


def test_one_chip_reads_as_before_the_per_chip_and_span_sums(summary):
    # the readings of the reduction before busy time was kept per chip and
    # gaps were labelled by program span, to the last digit
    assert summary.busy_s == 2.2317e-05
    assert summary.idle_share == 0.999044388797635
    assert summary.idle_by_label == {"other": 0.023331322999999977}
    assert summary.busy_by_chip == {"/device:TPU:0": summary.busy_s}
    assert summary.idle_by_span == {"other/-": 0.023331322999999977}


def test_innermost_span_of_nested_and_sibling_spans():
    spans = [(0, 100, "serve"), (10, 40, "resolve"), (15, 20, "cache"),
             (50, 90, "dispatch"), (60, 70, "upload"), (120, 130, "gc")]
    points = [5, 12, 17, 30, 45, 55, 65, 80, 95, 110, 125, 140]
    assert _innermost(spans, points) == [
        "serve", "resolve", "cache", "resolve", "serve", "dispatch",
        "upload", "dispatch", "serve", None, "gc", None]


def _plane(name, events, line="XLA Ops"):
    evs = [types.SimpleNamespace(start_ns=s, duration_ns=e - s, name=n)
           for s, e, n in events]
    return types.SimpleNamespace(
        name=name, lines=[types.SimpleNamespace(name=line, events=evs)])


def test_two_chips_busy_per_chip_and_gaps_by_phase_and_span(monkeypatch):
    """Window 0..1000 ns.  Host: phase `olap` over 0..600 with program
    spans `olap_serve` 0..500 holding `resolve` 100..300; phase `oltp`
    over 600..1000 with no span.  Chip 0 busy 300..500 and 900..1000;
    chip 1 busy 0..100."""
    host = _plane("/host:CPU", [
        (0, 1000, "bench:window"), (0, 600, "bench:olap"),
        (600, 1000, "bench:oltp"), (0, 500, "repro:olap_serve"),
        (100, 300, "repro:resolve")], line="python")
    planes = [host,
              _plane("/device:TPU:0", [(300, 500, "%a.1 = s32[8] add(x)"),
                                       (900, 1000, "%b.2 = s32[8] copy(x)")]),
              _plane("/device:TPU:1", [(0, 100, "%a.3 = s32[8] add(x)")])]
    monkeypatch.setattr(
        "jax.profiler.ProfileData.from_file",
        lambda path: types.SimpleNamespace(planes=planes))
    s = reduce_trace("made-up")
    assert s.n_devices == 2 and s.window_s == pytest.approx(1e-6)
    assert s.busy_by_chip == pytest.approx({"/device:TPU:0": 300e-9,
                                            "/device:TPU:1": 100e-9})
    assert s.busy_s == pytest.approx(200e-9)
    assert sum(s.busy_by_chip.values()) / 2 == pytest.approx(s.busy_s)
    # chip 0: gaps 0..300 (mid 150: olap/resolve), 500..900 (mid 700:
    # oltp/-); chip 1: 100..1000 (mid 550: olap/-)
    assert s.idle_by_span == pytest.approx({"olap/resolve": 300e-9,
                                            "oltp/-": 400e-9,
                                            "olap/-": 900e-9})
    assert s.idle_by_label == pytest.approx({"olap": 1200e-9,
                                             "oltp": 400e-9})
    assert [g[0] for g in s.idle_gaps] == ["olap/-", "oltp/-",
                                           "olap/resolve"]
    assert s.op_seconds == pytest.approx({"a:add": 300e-9,
                                          "b:copy": 100e-9})
