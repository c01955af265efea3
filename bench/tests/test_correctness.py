"""`correct` at a size a test run holds: sound runs pass, the controls (the
reference in the program's place with its snapshot guarantee broken; the
engine with first-committer-wins and certification off) fail, and so does
a whole run with the served path broken underneath."""

from __future__ import annotations

import io
import json

import pytest

from bench import run as bench_run
from bench.harness import Run, checks_pass

from .conftest import tiny_config


def _run_cell(workload: str, seed: int, *extra: str) -> tuple[dict, str]:
    args = bench_run.parse_args(["--workload", workload, "--seed", str(seed),
                                 "--seconds", "1", "--trace", "0", *extra])
    out, err = io.StringIO(), io.StringIO()
    assert bench_run.run_cell(args, require_tpu=False, out=out, err=err) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()


@pytest.mark.parametrize("workload", ["ch_w2_unified.adhoc",
                                      "ch_w2_decoupled.dashboards"])
def test_sound_run_is_correct_and_prints_its_checks(tiny_cells, workload):
    result, err = _run_cell(workload, 2**31 + 7)
    assert result["correct"] is True
    checks = result["checks"]
    for k in ("mismatched_results", "balance_drift", "order_id_drift",
              "orders_written_twice"):
        assert checks[k] == {"value": 0, "limit": 0}
    assert checks["results_checked"]["value"] >= 20
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {
        "oltp_commits_per_s", "olap_queries_per_s", "olap_query_p95_ms",
        "snapshot_staleness_p95_ms", "setup_s"}
    assert err.rstrip().splitlines()[-1].startswith("check results_checked")


@pytest.mark.parametrize("control,fails", [
    ("latest", ("mismatched_results",)),
    ("lost_updates", ("balance_drift", "order_id_drift",
                      "orders_written_twice"))])
@pytest.mark.parametrize("workload", ["ch_w2_unified.adhoc",
                                      "ch_w2_decoupled.dashboards"])
def test_control_is_not_correct(tiny_cells, workload, control, fails):
    result, _err = _run_cell(workload, 2**31 + 8, "--control", control)
    assert result["correct"] is False
    assert any(result["checks"][k]["value"] > 0 for k in fails)


def _answer_plus_one(monkeypatch):
    """An answer altered where it is produced: every finalized aggregate
    of the mirror and the views is off by one."""
    from repro.tensorstore import version_store
    real = version_store.finalize_agg
    monkeypatch.setattr(version_store, "finalize_agg",
                        lambda raw, op: real(raw, op) + 1)


def _half_the_blocks(monkeypatch):
    """Half of the batch left out: the scalar scan folds only the first
    half of its per-block partials."""
    from repro.kernels.rss_scan_agg import ops
    real = ops.fold_partials
    monkeypatch.setattr(ops, "fold_partials",
                        lambda p: real(p[: max(1, len(p) // 2)]))


def _state_unchanged(monkeypatch):
    """A step that returns its state unchanged: the paged mirror applies
    its first WAL record and leaves its state as it is for every later
    one."""
    from repro.tensorstore.mirror import PagedMirror
    real = PagedMirror.apply
    loaded = set()

    def apply(self, rec, *, gc_floor=0):
        if id(self) in loaded:
            return False
        loaded.add(id(self))
        return real(self, rec, gc_floor=gc_floor)
    monkeypatch.setattr(PagedMirror, "apply", apply)


@pytest.mark.parametrize("fault", [_answer_plus_one, _half_the_blocks,
                                   _state_unchanged],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_broken_served_path_is_not_correct(tiny_cells, monkeypatch, fault):
    fault(monkeypatch)
    result, _err = _run_cell("ch_w2_unified.adhoc", 2**31 + 9)
    assert result["correct"] is False
    assert result["checks"]["mismatched_results"]["value"] > 0


def test_reader_aborts_fail_the_check():
    run = Run(tiny_config("ch_w2_unified"), "adhoc", 5)
    run.window.olap_aborts = 1
    checks = run.check(sample=10)
    assert checks["olap_aborts"] == [1, 0]
    checks["results_checked"] = [10, 1]
    assert not checks_pass(checks)
