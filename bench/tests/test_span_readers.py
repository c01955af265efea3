"""The readers of the program's own spans and counters: each finds nothing
(None) where the program does not report its series, as a program without
`repro.obs.span` does not, and reads the right value from stubbed ones."""

from __future__ import annotations

import pytest

from bench import harness

TOTALS = {
    "rss_construct_seconds_sum": 0.5, "rss_construct_seconds_count": 20,
    "mirror_catch_up_seconds_sum": 0.25, "mirror_catch_up_seconds_count": 20,
    "serve_upload_seconds_sum": 1.5, "serve_maxabs_seconds_sum": 3.0,
    "mirror_h2d_bytes": 9_000_000,
    "oltp_commit_seconds_sum": 0.6, "oltp_commit_seconds_count": 1200,
    "gc_chains_visited": 40_000, "gc_chains_pruned": 300,
}
PLAN_SERVES = 300
EXPECTED = {
    "rss_construct_ms": 25.0,            # 0.5 s / 20 refreshes
    "mirror_catch_up_ms": 12.5,
    "serve_upload_ms": 5.0,              # 1.5 s / 300 plans served
    "serve_maxabs_ms": 10.0,
    "h2d_bytes_per_serve": 30_000.0,
    "oltp_commit_ms": 0.5,
    "gc_useful_chain_share": 0.75,       # 100 * 300 / 40k
}


def _input(totals: dict, plan_serves: int) -> harness.LayerInput:
    w = harness.Window(t0=0.0, t1=30.0, plan_serves=plan_serves)
    return harness.LayerInput(window=w, spans={}, totals=totals, stages={},
                              trace=None, peaks={})


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_finds_nothing_without_the_series(metric):
    read = harness.load_reader(metric)
    assert read(_input({}, PLAN_SERVES)) is None
    # the parent's registry totals: counters and gauges only
    assert read(_input({"engine_commits": 5}, PLAN_SERVES)) is None


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_value_from_stubbed_series(metric):
    read = harness.load_reader(metric)
    assert read(_input(dict(TOTALS), PLAN_SERVES)) == \
        pytest.approx(EXPECTED[metric])


@pytest.mark.parametrize("metric", ["serve_upload_ms", "serve_maxabs_ms",
                                    "h2d_bytes_per_serve"])
def test_per_serve_readers_need_a_served_plan(metric):
    assert harness.load_reader(metric)(_input(dict(TOTALS), 0)) is None


def test_empty_counts_read_as_nothing():
    totals = dict(TOTALS, rss_construct_seconds_count=0,
                  gc_chains_visited=0)
    assert harness.load_reader("rss_construct_ms")(
        _input(totals, PLAN_SERVES)) is None
    assert harness.load_reader("gc_useful_chain_share")(
        _input(totals, PLAN_SERVES)) is None
