"""`scripts/span_idle.py`: idle device time labelled by the innermost
program span, from a kept profiler trace."""

import importlib.util
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
XPLANE = ROOT / "bench" / "tests" / "data" / "scan_window.xplane.pb"


@pytest.fixture(scope="module")
def span_idle():
    spec = importlib.util.spec_from_file_location(
        "span_idle", ROOT / "scripts" / "span_idle.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_innermost_span_of_nested_and_sibling_spans(span_idle):
    spans = [(0, 100, "serve"), (10, 40, "resolve"), (15, 20, "cache"),
             (50, 90, "dispatch"), (60, 70, "upload"), (120, 130, "gc")]
    points = [5, 12, 17, 30, 45, 55, 65, 80, 95, 110, 125, 140]
    assert span_idle._innermost(spans, points) == [
        "serve", "resolve", "cache", "resolve", "serve", "dispatch",
        "upload", "dispatch", "serve", None, "gc", None]


def test_idle_by_span_agrees_with_the_benchmark_reduction(span_idle):
    from bench.trace_reduce import reduce_trace
    got = span_idle.idle_by_span(str(XPLANE))
    want = reduce_trace(str(XPLANE))
    assert got["window_s"] == pytest.approx(want.window_s)
    assert got["busy_s"] == pytest.approx(want.busy_s)
    # a trace with no program spans: every gap is labelled by phase alone
    assert got["repro_spans"] == 0
    assert sum(got["idle_s_by_span"].values()) == pytest.approx(
        sum(want.idle_by_label.values()))
    assert {k.split("/")[0] for k in got["idle_s_by_span"]} == \
        set(want.idle_by_label)
    assert all(k.endswith("/-") for k in got["idle_s_by_span"])
