"""Shared helpers of the benchmark's CPU tests: the cells' configurations cut
to a tiny schema, so a whole run (load, warm-up, window, check) takes a
few seconds with the Pallas kernels in interpret mode."""

from __future__ import annotations

import copy
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_SCHEMA = {"warehouses": 1, "districts": 4, "customers": 20,
               "items": 80, "order_capacity": 8}


from bench.harness import load_config  # noqa: E402


def tiny_config(name: str) -> dict:
    cfg = copy.deepcopy(load_config(name))
    cfg["schema"] = dict(TINY_SCHEMA)
    return cfg


@pytest.fixture
def tiny_cells(monkeypatch):
    """`bench.harness.load_config` answering every configuration at the
    tiny schema, and no persistent compile cache (a test process keeps
    JAX's global config for the tests that follow it)."""
    import bench.harness
    import bench.run
    monkeypatch.setattr(bench.harness, "load_config", tiny_config)
    monkeypatch.setattr(bench.run, "configure_compile_cache", lambda: "off")
    return tiny_config
