"""The bytes a scan needs come from the plan alone, the same for every
kernel mode that serves it; the peak table refuses an unknown device."""

from __future__ import annotations

import pytest

from bench import cost
from bench.harness import Run

from .conftest import tiny_config


def test_scan_bytes_per_key_and_member():
    keys = tuple(f"stock:0:{i}" for i in range(100))
    assert cost.scan_bytes(("agg", keys, ("sum", "int", None)), 0) == \
        100 * 96
    assert cost.scan_bytes(("multi", keys, ()), 3) == 100 * 96 + 12
    groups = (keys[:10], keys[5:25])
    assert cost.spec_keys(("group", groups, ())) == 30
    assert cost.scan_bytes(("group", groups, ()), 1, slots=4) == \
        30 * 4 * 12 + 4


def test_peaks_by_device_kind():
    v5e = cost.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16 * 2**30
    assert "source" in v5e
    with pytest.raises(KeyError):
        cost.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        cost.peaks("cpu")


@pytest.fixture(scope="module")
def run():
    r = Run(tiny_config("ch_w2_unified"), "adhoc", 99)
    r.load()
    r.warm_up(60)
    return r


@pytest.mark.parametrize("mode", ["flat", "chunked"])
def test_kernel_bytes_do_not_depend_on_the_kernel_mode(run, mode,
                                                       monkeypatch):
    """One grouped plan, served through the flat and the chunked kernels,
    is charged the same bytes: those of its keys at the served snapshot."""
    from repro.kernels.rss_scan_agg.ops import LAUNCH_STATS

    monkeypatch.setenv("REPRO_GROUPED_MODE", mode)
    sc = run.schema
    groups = tuple(sc.order_range(0, d) for d in range(sc.districts))
    spec = ("group", groups, (("sum", "total", None),
                              ("count_above", "total", 20)))
    ctx = run.htap.olap_begin()
    before = LAUNCH_STATS[mode]
    run.window = type(run.window)()
    run.recording = True
    try:
        result = run.serve(ctx, spec)
    finally:
        run.recording = False
        run.htap.olap_commit(ctx)
    assert LAUNCH_STATS[mode] == before + 1
    n_members = len(run.window.served[0][1][1])
    assert run.window.kernel_bytes == cost.scan_bytes(spec, n_members)
    assert run.window.kernel_bytes == sc.districts * 8 * 96 + 4 * n_members
    floor, members = run.window.served[0][1]
    assert result == run.ref.evaluate(spec, floor, members)
