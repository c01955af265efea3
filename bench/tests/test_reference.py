"""The plain reference against the program's chain oracle (per-key engine
reads at the served snapshot), and the staleness and percentile
arithmetic of the end-to-end metrics."""

from __future__ import annotations

import random

import numpy as np
import pytest

from bench.harness import Run, _snap_of, percentile
from bench.reference import Reference, consistency, same

from .conftest import tiny_config


@pytest.mark.parametrize("config", ["ch_w2_unified", "ch_w2_decoupled"])
def test_reference_matches_the_chain_oracle(config):
    run = Run(tiny_config(config), "adhoc", 2**31 + 12345)
    run.load()
    run.warm_up(120)
    htap, sc = run.htap, run.schema
    if run.unified:
        ctx = htap.olap_begin()
        snapshot = ctx.rss
    else:
        ctx = htap.olap_snapshot()
        snapshot = ctx[3]
    floor, members = _snap_of(snapshot)
    keys = tuple(sc.key_families())
    chain = [htap.olap_read(ctx, k) for k in keys]
    assert same(chain, run.ref.evaluate(("scan", keys), floor, members))
    stock = [v for k, v in zip(keys, chain) if k.startswith("stock:")]
    for op, want in ((("sum", "int", None), sum(stock)),
                     (("count_below", "int", 60),
                      sum(v < 60 for v in stock)),
                     (("min", "int", None), min(stock))):
        assert run.ref.evaluate(("agg", sc.stock_keys, op), floor,
                                members) == want
    # the run's terminals committed past the load, so an older snapshot
    # reads differently from the newest acknowledged state
    assert run.ref.last_seq > floor


def test_reference_visibility_by_floor_and_members():
    ref = Reference()
    ref.commit(1, [("a", 1), ("b", {"total": 5})], 0.0)
    ref.commit(2, [("a", 2)], 1.0)
    ref.commit(3, [("a", 3), ("c", 7)], 2.0)
    assert ref.evaluate(("scan", ("a", "b", "c", "d")), 1) == \
        [1, {"total": 5}, 0, 0]
    assert ref.evaluate(("scan", ("a", "c")), 1, (3,)) == [3, 7]
    assert ref.evaluate(("agg", ("a", "c"), ("sum", "int", None)), 2) == 2
    assert ref.evaluate(("group", (("a",), ("b",)),
                         (("sum", "total", None), ("count", "total", None))),
                        3) == ((0, 0), (5, 1))
    assert ref.evaluate(("multi", ("a", "c", "d"),
                         (("min", "int", None), ("max", "int", None),
                          ("count_above", "int", 2),
                          ("sum_below", "int", 7))), 3) == (0, 7, 2, 3)
    with pytest.raises(ValueError):
        ref.commit(3, [("a", 4)], 3.0)


def test_staleness_is_the_age_of_the_oldest_unseen_acknowledgement():
    ref = Reference()
    for seq, t in ((1, 10.0), (2, 11.0), (4, 12.0), (6, 13.0)):
        ref.commit(seq, [("k", seq)], t)
    assert ref.staleness(6, (), 20.0) == 0.0
    assert ref.staleness(2, (), 20.0) == pytest.approx(8.0)
    assert ref.staleness(2, (4,), 20.0) == pytest.approx(7.0)
    assert ref.staleness(2, (4, 6), 20.0) == 0.0
    # a commit acknowledged after the serve is not owed to it
    assert ref.staleness(4, (), 12.5) == 0.0
    assert ref.staleness(1, (), 11.5) == pytest.approx(0.5)


@pytest.mark.parametrize("settle", [True, False], ids=["settled", "not"])
@pytest.mark.parametrize("config", ["ch_w2_unified", "ch_w2_decoupled"])
def test_settle_rounds_take_in_a_set_up_pause(config, settle):
    """A pause in set-up (the kernel warm-up) shows in the window's
    staleness unless the settle rounds run after it: here the pause is
    made by moving every acknowledgement so far 100 s into the past."""
    run = Run(tiny_config(config), "adhoc", 2**31 + 77)
    run.load()
    run.warm_up(40)
    run.ref.ack_t[:] = [t - 100.0 for t in run.ref.ack_t]
    if settle:
        run.warm_up(run.settle_rounds())
    w = run.measure(0.5)
    worst = max(run.ref.staleness(snap[0], snap[1], t)
                for _spec, snap, _r, t in w.served)
    assert (worst < 50.0) == settle


def test_percentile_is_numpys_linear():
    rng = random.Random(3)
    for n in (1, 2, 5, 20, 401):
        xs = [rng.expovariate(1.0) for _ in range(n)]
        for q in (0, 50, 95, 100):
            assert percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    with pytest.raises(ValueError):
        percentile([], 95)


def _history(*commits):
    """A reference holding an initial load of two customers, their
    warehouse, one district and its orders below `next_o_id` 2, then the
    given commits of (key, value) writes."""
    ref = Reference()
    ref.commit(1, [("warehouse:0", 0), ("customer:0:0:0", 1000),
                   ("customer:0:0:1", 1000),
                   ("district:0:0", {"next_o_id": 2, "ytd": 0}),
                   ("order:0:0:0", {"total": 3}),
                   ("order:0:0:1", {"total": 4})], 0.0)
    for seq, writes in enumerate(commits, start=2):
        ref.commit(seq, writes, float(seq))
    return ref


_PAY_A = [("warehouse:0", 10), ("customer:0:0:0", 990)]
_PAY_B_SERIAL = [("warehouse:0", 30), ("customer:0:0:1", 980)]
_PAY_B_LOST = [("warehouse:0", 20), ("customer:0:0:1", 980)]
_NEW_ORDER_2 = [("district:0:0", {"next_o_id": 3, "ytd": 0}),
                ("order:0:0:2", {"total": 5})]
_NEW_ORDER_3 = [("district:0:0", {"next_o_id": 4, "ytd": 0}),
                ("order:0:0:3", {"total": 6})]


@pytest.mark.parametrize("commits,broken", [
    ((_PAY_A, _PAY_B_SERIAL, _NEW_ORDER_2, _NEW_ORDER_3), set()),
    # the second payment read the warehouse before the first committed
    ((_PAY_A, _PAY_B_LOST), {"balance_drift"}),
    # two new-orders read next_o_id 2 and both took order 2
    ((_NEW_ORDER_2, _NEW_ORDER_2), {"order_id_drift",
                                    "orders_written_twice"}),
], ids=["serial", "lost_payment", "order_taken_twice"])
def test_consistency_conditions(commits, broken):
    got = consistency(_history(*commits))
    assert {k for k, v in got.items() if v} == broken
    assert got["balance_drift"] == (10 if broken == {"balance_drift"}
                                    else 0)
