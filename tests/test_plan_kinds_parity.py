"""The served paged path against the chain-store oracle, for every plan kind.

One seeded TPC-C-shaped commit stream is loaded into a single-node
`ssi+rss` facade with a paged mirror.  A reader pins the RSS snapshot
after the first half of the stream; the second half then commits and the
mirror catches up past it, so the pinned snapshot must exclude newer
versions that sit in the same pages.  Every case executes one plan on the
`PagedVersionStore` (mirror plus kernel ops, interpret mode on the CPU)
and on the `ChainVersionStore` over the engine's chains, at that one
snapshot, and asserts the answers are equal.
"""

import random

import pytest

from repro.kernels.rss_scan_agg import ops as kops
from repro.mvcc import SingleNodeHTAP
from repro.mvcc.workload import Scale, load_initial, oltp_transaction
from repro.tensorstore import (AggOp, AggPlan, BatchPlan, ChainVersionStore,
                               GroupByPlan, MultiAggPlan, ScanPlan)

SC = Scale()
KINDS = ("sum", "count", "count_below", "min", "max", "count_above",
         "sum_below")
THRESHOLD = 50
STOCK = tuple(SC.all_stock_keys())
ORDERS = tuple(k for w in range(SC.warehouses) for d in range(SC.districts)
               for k in SC.order_range_keys(w, d))
FIELD_KEYS = {"int": STOCK, "total": ORDERS}


def _op(kind: str, field: str) -> AggOp:
    return AggOp(kind, field, THRESHOLD if kind in
                 ("count_below", "count_above", "sum_below") else None)


def _commit_stream(htap: SingleNodeHTAP, rng: random.Random, n: int) -> None:
    """`n` workload transactions, one at a time (so none aborts)."""
    eng = htap.engine
    for _ in range(n):
        gen, _name = oltp_transaction(rng, SC)
        t = eng.begin()
        val = None
        try:
            while True:
                step = gen.send(val)
                val = None
                if step[0] == "r":
                    val = eng.read(t, step[1])
                elif step[0] == "w":
                    eng.write(t, step[1], step[2])
        except StopIteration:
            pass
        eng.commit(t)


@pytest.fixture(scope="module")
def world():
    htap = SingleNodeHTAP("ssi+rss", paged=True,
                          reserve_keys=SC.key_families())
    load_initial(htap.engine, SC)
    rng = random.Random(7)
    _commit_stream(htap, rng, 60)
    htap.refresh_rss()
    rid, snap = htap.prot.acquire()     # a reader pins the snapshot
    _commit_stream(htap, rng, 60)
    htap.refresh_rss()                  # the mirror moves past the pin
    chain = ChainVersionStore(htap.engine.store)
    latest = htap.rss_manager.snapshot
    # the second half wrote keys the pinned snapshot must not see
    assert chain.scan(STOCK, snap) != chain.scan(STOCK, latest)
    assert chain.scan(ORDERS, snap) != chain.scan(ORDERS, latest)
    yield htap.paged_store, chain, snap
    htap.prot.release(rid)


def _assert_parity(world, plan, *, kernel: bool = True):
    paged, chain, snap = world
    calls = kops.LAUNCH_STATS["pallas_calls"]
    got = paged.execute(plan, snap)
    assert got == chain.execute(plan, snap)
    if kernel:
        assert kops.LAUNCH_STATS["pallas_calls"] > calls
    return got


@pytest.mark.parametrize("field", ["int", "total"])
@pytest.mark.parametrize("kind", KINDS)
def test_agg_plan(world, kind, field):
    _assert_parity(world, AggPlan(FIELD_KEYS[field], _op(kind, field)))


@pytest.mark.parametrize("kind", KINDS)
def test_group_by_plan(world, kind):
    """40 groups of stock and order keys (the chunked strategy), each
    reduced on both fields."""
    groups = tuple(STOCK[i:i + 5] + ORDERS[i // 5 * 3:i // 5 * 3 + 3]
                   for i in range(0, len(STOCK), 5))
    assert len(groups) > kops.FLAT_MODE_MAX_GROUPS
    out = _assert_parity(world, GroupByPlan(groups, (_op(kind, "int"),
                                                     _op(kind, "total"))))
    assert len(out) == len(groups)


def test_multi_agg_plan(world):
    ops = tuple(_op(k, f) for k in KINDS for f in ("int", "total"))
    out = _assert_parity(world, MultiAggPlan(STOCK + ORDERS, ops))
    assert len(out) == len(ops)


def test_batch_plan(world):
    plans = (AggPlan(STOCK, _op("sum_below", "int")),
             MultiAggPlan(ORDERS, (_op("count_above", "total"),
                                   _op("max", "total"))),
             GroupByPlan((STOCK[:50], STOCK[50:], ORDERS),
                         (_op("min", "int"), _op("sum", "total"))))
    out = _assert_parity(world, BatchPlan(plans))
    assert len(out) == len(plans)


def test_scan_plan(world):
    keys = (tuple(f"warehouse:{w}" for w in range(SC.warehouses))
            + tuple(SC.all_district_keys()) + STOCK[:40] + ORDERS[:40])
    out = _assert_parity(world, ScanPlan(keys), kernel=False)
    assert len(out) == len(keys)
