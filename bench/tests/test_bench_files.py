"""The harness finds every configuration, mix and per-layer metric by name
from its file alone, and `BENCHMARK.json` keeps to the benchmark's
contract."""

from __future__ import annotations

import json
import re
import types
from pathlib import Path

import pytest

from bench import harness, run
from bench.traffic import Mix, Schema

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_every_cell_resolves_from_files(cell):
    cfg = harness.load_config(cell["config"])
    mix = Mix.load(cell["traffic"], Schema.from_config(cfg))
    assert mix.name == cell["traffic"]
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    _bench, found = run.find_cell(cell["name"])
    assert found == cell


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_every_layer_metric_has_a_reader(metric):
    assert callable(harness.load_reader(metric["name"]))
    assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    cells = {c["name"] for c in BENCH["workloads"]}
    assert set(metric["workloads"]) <= cells


def test_contract_shape():
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for cfg in BENCH["configs"]:
        assert (ROOT / cfg["file"]).is_file()
        file_cfg = json.loads((ROOT / cfg["file"]).read_text())
        assert set(cfg["reduced"]) <= set(file_cfg)


def test_new_files_alone_add_a_configuration_a_mix_and_a_metric(tmp_path):
    """A later cell adds files and entries only: the loaders take the same
    directory layout from any root."""
    base = harness.load_config("ch_w2_unified")
    (tmp_path / "configs").mkdir()
    (tmp_path / "mixes").mkdir()
    (tmp_path / "layers").mkdir()
    cfg = dict(base, name="ch_w3_unified",
               schema=dict(base["schema"], warehouses=3))
    (tmp_path / "configs" / "ch_w3_unified.json").write_text(json.dumps(cfg))
    (tmp_path / "mixes" / "stock_only.json").write_text(json.dumps({
        "name": "stock_only", "warmup_rounds": 5,
        "oltp": {"payment": 1},
        "olap": [{"shape": "stock_level", "cards": 1,
                  "params": {"threshold": [10, 20]}}]}))
    (tmp_path / "layers" / "window_rounds.py").write_text(
        "def read(li):\n    return li.window.plan_serves or None\n")

    found = harness.load_config("ch_w3_unified", root=tmp_path)
    assert found["schema"]["warehouses"] == 3
    mix = Mix.load("stock_only", Schema.from_config(found), root=tmp_path)
    assert mix.warmup_rounds == 5 and len(mix.queries) == 1
    reader = harness.load_reader("window_rounds", root=tmp_path)
    li = types.SimpleNamespace(window=harness.Window(plan_serves=7))
    assert reader(li) == 7
    li.window.plan_serves = 0
    assert reader(li) is None


def test_a_file_that_names_another_configuration_is_refused(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "a.json").write_text(json.dumps({"name": "b"}))
    with pytest.raises(ValueError):
        harness.load_config("a", root=tmp_path)


def test_facade_options_reach_the_facade(monkeypatch):
    """A configuration's `facade` object is passed to the facade's
    constructor beside the options the harness sets."""
    from repro.mvcc import htap
    got = {}

    class Facade:
        def __init__(self, *args, **kwargs):
            got.update(kwargs)
            self.engine = self.primary = None
    monkeypatch.setattr(htap, "SingleNodeHTAP", Facade)
    monkeypatch.setattr(htap, "MultiNodeHTAP", Facade)
    for name in ("ch_w2_unified", "ch_w2_decoupled"):
        got.clear()
        cfg = dict(harness.load_config(name), facade={"replica_chip": 3})
        harness.Run(cfg, "adhoc", 1)
        assert got["replica_chip"] == 3
        assert got["certifier"] == cfg["certifier"]


@pytest.mark.parametrize("key", sorted(harness.HARNESS_OPTIONS))
def test_a_facade_option_the_harness_sets_is_refused(tmp_path, key):
    (tmp_path / "configs").mkdir()
    cfg = dict(harness.load_config("ch_w2_decoupled"), facade={key: 1})
    (tmp_path / "configs" / "ch_w2_decoupled.json").write_text(
        json.dumps(cfg))
    with pytest.raises(ValueError, match=key):
        harness.load_config("ch_w2_decoupled", root=tmp_path)
    with pytest.raises(ValueError, match=key):
        harness.Run(cfg, "adhoc", 1)
