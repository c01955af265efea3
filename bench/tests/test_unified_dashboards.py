"""`ch_w2_unified.dashboards` on the CPU at the tiny schema: the run is
`correct`, no program is built in the measured window, and every
per-layer metric the cell lists that the program feeds reads a number."""

from __future__ import annotations

import pytest

from bench.tests.test_decoupled_4chip import program_metrics, run_tiny

CELL = "ch_w2_unified.dashboards"


@pytest.fixture(scope="module")
def dashboards_run():
    return run_tiny(CELL, 1)


def test_the_run_is_correct_and_builds_nothing_in_the_window(
        dashboards_run):
    run, result = dashboards_run
    assert result["correct"] is True, result["checks"]
    assert result["checks"]["mismatched_results"]["value"] == 0
    assert "inside it 0 compiles and 0 cache loads" in run["err"]


@pytest.mark.parametrize("metric", program_metrics(CELL))
def test_every_listed_program_metric_reads_a_number(dashboards_run, metric):
    _run, result = dashboards_run
    assert isinstance(result["metrics"][metric]["value"], (int, float))
