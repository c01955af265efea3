"""Whole-batch plan fusion + shape-dispatched kernel selection.

The tentpole contracts of this PR:

  * a `BatchPlan` of N same-horizon aggregate plans produces EXACTLY the
    N unbatched results and the chain oracle's — at the mirror, at both
    HTAP facades, and through the driver's round-level batcher — while
    costing ONE fused aggregate dispatch (and one/two pallas calls,
    depending on the strategy the shape dispatcher picks);
  * `select_grouped_mode` routes (P, G, n_plans) shapes between host /
    flat / chunked, overridable per call or via REPRO_GROUPED_MODE;
  * the int32 overflow guards hold: past the 8-page block bound the ops
    take per-page partials, chunked demotes to flat when the whole-scan
    bound fails — results stay exact throughout.
"""

import random

import pytest

from repro.kernels.rss_scan_agg import ops as kops
from repro.mvcc import Engine, MultiNodeHTAP, SingleNodeHTAP
from repro.mvcc.driver import run_multi_node, run_single_node
from repro.mvcc.workload import Scale, load_initial
from repro.tensorstore import (AggOp, AggPlan, BatchPlan, ChainVersionStore,
                               GroupByPlan, MultiAggPlan, PagedMirror,
                               PagedVersionStore, ScanPlan, apply_plan,
                               plan_keys)

OPS = (AggOp("sum", "int"), AggOp("count", "int"),
       AggOp("count_below", "int", 40), AggOp("min", "int"),
       AggOp("max", "int"), AggOp("sum", "total"))


def _loaded_engine(n=24, seed=0):
    eng = Engine("ssi")
    rng = random.Random(seed)
    t = eng.begin()
    for i in range(n):
        eng.write(t, f"k:{i}", rng.randrange(-80, 120))
    for i in range(4):
        eng.write(t, f"o:{i}", {"items": [], "total": rng.randrange(200)})
    eng.commit(t)
    return eng


def _mirror_for(eng):
    mirror = PagedMirror()
    mirror.catch_up(eng.wal)
    return mirror


def _plans(rng, n, pool):
    out = []
    for _ in range(n):
        kind = rng.randrange(3)
        ops = tuple(rng.sample(OPS, rng.randint(1, 3)))
        if kind == 0:
            out.append(AggPlan(tuple(rng.sample(pool, 5)), ops[0]))
        elif kind == 1:
            out.append(MultiAggPlan(tuple(rng.sample(pool, 6)), ops))
        else:
            groups = tuple(tuple(rng.sample(pool, rng.randint(0, 4)))
                           for _ in range(rng.randint(1, 4)))
            out.append(GroupByPlan(groups, ops))
    return out


# --------------------------------------------------------- mirror-level fusion
class TestMirrorBatchFusion:
    @pytest.mark.parametrize("mode", [None, "flat", "chunked"])
    @pytest.mark.parametrize("seed", range(3))
    def test_batch_equals_unbatched_and_oracle(self, seed, mode):
        eng = _loaded_engine(seed=seed)
        mirror = _mirror_for(eng)
        mirror.grouped_mode = mode
        paged = PagedVersionStore(mirror)
        chain = ChainVersionStore(eng.store)
        rng = random.Random(seed)
        pool = [f"k:{i}" for i in range(24)] + [f"o:{i}" for i in range(4)] \
            + ["missing:x"]
        plans = _plans(rng, 4, pool)
        batch = BatchPlan(tuple(plans))
        got, gw = paged.execute_with_writers(batch, eng.seq)
        want, ww = chain.execute_with_writers(batch, eng.seq)
        assert tuple(got) == tuple(want)
        assert gw == ww
        # exactly the per-plan unbatched results, in order
        for plan, r in zip(plans, got):
            assert r == paged.execute(plan, eng.seq)
            assert r == chain.execute(plan, eng.seq)

    def test_single_plan_batch_equals_unbatched(self):
        eng = _loaded_engine()
        paged = PagedVersionStore(_mirror_for(eng))
        plan = MultiAggPlan(tuple(f"k:{i}" for i in range(10)), OPS[:3])
        (only,), writers = paged.execute_with_writers(
            BatchPlan((plan,)), eng.seq)
        assert only == paged.execute(plan, eng.seq)
        assert writers == paged.execute_with_writers(plan, eng.seq)[1]

    def test_batch_costs_one_fused_dispatch(self):
        eng = _loaded_engine()
        mirror = _mirror_for(eng)
        paged = PagedVersionStore(mirror)
        plans = tuple(AggPlan(tuple(f"k:{i + 4 * j}" for i in range(4)),
                              AggOp("sum", "int")) for j in range(4))
        before = dict(mirror.exec_stats)
        paged.execute(BatchPlan(plans), eng.seq)
        assert mirror.exec_stats["agg_dispatches"] - \
            before["agg_dispatches"] == 1
        assert mirror.exec_stats["batches"] - before["batches"] == 1
        assert mirror.exec_stats["batched_plans"] - \
            before["batched_plans"] == 4

    @pytest.mark.parametrize("mode,calls", [("flat", 1), ("chunked", 2)])
    def test_batch_pallas_call_count_per_mode(self, mode, calls):
        """Flat = one fused launch for the whole batch; chunked = two
        (select + tiled reduce), never one per plan."""
        eng = _loaded_engine()
        mirror = _mirror_for(eng)
        mirror.grouped_mode = mode
        paged = PagedVersionStore(mirror)
        plans = tuple(MultiAggPlan(tuple(f"k:{i + 6 * j}" for i in range(6)),
                                   (AggOp("sum", "int"),
                                    AggOp("count", "int")))
                      for j in range(4))
        kops.reset_launch_stats()
        paged.execute(BatchPlan(plans), eng.seq)
        assert kops.LAUNCH_STATS["pallas_calls"] == calls
        assert kops.LAUNCH_STATS["dispatches"] == 1
        assert kops.LAUNCH_STATS[mode] == 1

    def test_batch_rejects_scan_plans(self):
        with pytest.raises(AssertionError):
            BatchPlan((ScanPlan(("a",)),))
        with pytest.raises(AssertionError):
            BatchPlan(())


# --------------------------------------------------------------- facade level
class TestFacadeBatch:
    def _single(self):
        htap = SingleNodeHTAP("ssi+rss", paged=True, check_scans=True,
                              reserve_keys=Scale().key_families())
        load_initial(htap.engine, Scale())
        htap.refresh_rss()
        return htap

    def test_single_node_batch_equals_unbatched_and_records_reads(self):
        htap = self._single()
        keys = Scale().all_stock_keys()
        txns = [htap.olap_begin() for _ in range(4)]
        assert len({t.rss.lsn for t in txns}) == 1    # PRoT pin sharing
        plans = [MultiAggPlan(tuple(keys[8 * i:8 * i + 8]), OPS[:3])
                 for i in range(4)]
        results = htap.olap_execute_batch(list(zip(txns, plans)))
        for t, p, r in zip(txns, plans, results):
            t2 = htap.olap_begin()
            assert r == htap.olap_execute(t2, p)
            assert set(t.reads) == set(plan_keys(p))  # read set recorded
            htap.olap_commit(t2)
        for t in txns:
            htap.olap_commit(t)

    def test_single_node_mixed_horizons_fall_back(self):
        htap = self._single()
        t1 = htap.olap_begin()
        t2 = htap.engine.begin()
        htap.engine.write(t2, "stock:0:0", 999)
        htap.engine.commit(t2)
        htap.refresh_rss()
        t3 = htap.olap_begin()
        if t1.rss.lsn == t3.rss.lsn:        # horizons happened to match
            pytest.skip("no horizon split to exercise")
        plan = AggPlan(("stock:0:0", "stock:0:1"), AggOp("sum", "int"))
        before = htap.mirror.exec_stats["batches"]
        r1, r3 = htap.olap_execute_batch([(t1, plan), (t3, plan)])
        assert htap.mirror.exec_stats["batches"] == before  # no fused batch
        assert r1 == htap.olap_execute(t1, plan)
        assert r3 == htap.olap_execute(t3, plan)

    def test_multi_node_batch_equals_unbatched(self):
        htap = MultiNodeHTAP("ssi+rss", paged_olap=True, check_scans=True,
                             n_replicas=2,
                             reserve_keys=Scale().key_families())
        load_initial(htap.primary, Scale())
        htap.ship_log()
        keys = Scale().all_stock_keys()
        snaps = [htap.olap_snapshot() for _ in range(3)]
        plans = [GroupByPlan((tuple(keys[:6]), tuple(keys[6:12])),
                             (AggOp("sum", "int"), AggOp("max", "int")))
                 for _ in range(3)]
        entries = list(zip(snaps, plans))
        results = htap.olap_execute_batch(entries)
        for (h, p), r in zip(entries, results):
            assert r == htap.olap_execute(h, p)
        for h in snaps:
            htap.olap_release(h)


# --------------------------------------------------------------- driver level
class TestDriverBatching:
    def test_single_node_run_batches_and_stays_correct(self):
        m = run_single_node(olap_mode="ssi+rss", oltp_clients=4,
                            olap_clients=4, rounds=800, seed=11,
                            olap_scan=True, paged_olap=True,
                            check_scans=True, batch_plans=True)
        assert m.olap_batch_dispatches > 0
        assert m.plans_per_dispatch() > 1.0
        assert m.olap_agg_dispatches > 0
        assert m.olap_mode_flat + m.olap_mode_chunked + m.olap_mode_host > 0

    def test_multi_node_run_batches_and_stays_correct(self):
        m = run_multi_node(olap_mode="ssi+rss", oltp_clients=4,
                           olap_clients=4, rounds=600, seed=11,
                           olap_scan=True, paged_olap=True,
                           check_scans=True, n_replicas=2,
                           batch_plans=True)
        assert m.olap_batch_dispatches > 0
        assert m.plans_per_dispatch() > 1.0

    def test_batched_run_matches_unbatched_outputs(self):
        kw = dict(olap_mode="ssi+rss", oltp_clients=3, olap_clients=2,
                  rounds=600, seed=5, olap_scan=True, paged_olap=True)
        a = run_single_node(**kw, batch_plans=False)
        b = run_single_node(**kw, batch_plans=True)
        assert a.olap_outputs == b.olap_outputs   # same results, fewer
        assert a.oltp_commits == b.oltp_commits   # launches


# ----------------------------------------------------------- shape dispatcher
class TestSelectGroupedMode:
    def test_shape_heuristic(self):
        assert kops.select_grouped_mode(32, 4, 1) == "host"
        assert kops.select_grouped_mode(32, 4, 2) == "flat"   # batches fuse
        assert kops.select_grouped_mode(
            4096, kops.FLAT_MODE_MAX_GROUPS, 1) == "flat"
        assert kops.select_grouped_mode(
            4096, kops.FLAT_MODE_MAX_GROUPS + 1, 1) == "chunked"
        assert kops.select_grouped_mode(4096, 256, 4) == "chunked"

    def test_override_wins(self):
        assert kops.select_grouped_mode(32, 4, 1,
                                        override="chunked") == "chunked"
        with pytest.raises(AssertionError):
            kops.select_grouped_mode(32, 4, 1, override="nope")

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv(kops.GROUPED_MODE_ENV, "flat")
        assert kops.select_grouped_mode(32, 4, 1) == "flat"
        monkeypatch.setenv(kops.GROUPED_MODE_ENV, "auto")
        assert kops.select_grouped_mode(32, 4, 1) == "host"

    def test_mirror_honors_env_override(self, monkeypatch):
        eng = _loaded_engine()
        monkeypatch.setenv(kops.GROUPED_MODE_ENV, "chunked")
        mirror = _mirror_for(eng)
        paged = PagedVersionStore(mirror)
        plan = GroupByPlan((("k:0", "k:1"), ("k:2",)),
                           (AggOp("sum", "int"),))
        before = mirror.exec_stats["mode_chunked"]
        got = paged.execute(plan, eng.seq)
        assert mirror.exec_stats["mode_chunked"] == before + 1
        assert got == ChainVersionStore(eng.store).execute(plan, eng.seq)


# ------------------------------------------------------------ overflow guards
class TestOverflowGuards:
    def test_safe_block_pages_halves(self):
        """The ladder never goes below one 8-page sublane tile: past the
        8-page bound it returns None (the exact per-page fallback)."""
        assert kops.safe_block_pages(100) == 8
        assert kops.safe_block_pages((2**31 - 1) // 8) == 8
        assert kops.safe_block_pages(2**28 + 1) is None
        assert kops.safe_block_pages(2**31 - 1) is None

    def test_scan_bound(self):
        assert kops.scan_bound_ok(100, 4096)
        assert not kops.scan_bound_ok(2**28, 16)
        assert kops.scan_bound_ok(0, 0)

    @pytest.mark.parametrize("big,fallbacks,pallas", [
        (2**27 + 7, 1, 1),      # scan bound fails, 8-page block bound holds
        (2**28 + 7, 2, 0),      # both fail: flat takes per-page partials
    ])
    def test_chunked_demotes_to_flat_on_scan_bound(self, big, fallbacks,
                                                   pallas):
        """Huge field values violate the whole-scan device-fold bound:
        a chunked pick silently demotes to flat (exact host fold) and the
        result still equals the arbitrary-precision oracle.  Past the
        8-page block bound the flat op computes per-page partials with
        the jnp reference instead of a thinner kernel block."""
        eng = Engine("ssi")
        t = eng.begin()
        for i in range(24):
            eng.write(t, f"k:{i}", big if i % 2 else -big)
        eng.commit(t)
        mirror = _mirror_for(eng)
        mirror.grouped_mode = "chunked"
        paged = PagedVersionStore(mirror)
        plan = GroupByPlan((tuple(f"k:{i}" for i in range(12)),
                            tuple(f"k:{i}" for i in range(12, 24))),
                           (AggOp("sum", "int"), AggOp("min", "int")))
        kops.reset_launch_stats()
        got = paged.execute(plan, eng.seq)
        assert kops.LAUNCH_STATS["overflow_fallbacks"] == fallbacks
        assert kops.LAUNCH_STATS["flat"] == 1          # demoted
        assert kops.LAUNCH_STATS["pallas_calls"] == pallas
        assert got == ChainVersionStore(eng.store).execute(plan, eng.seq)

    def test_scalar_agg_past_block_bound_is_exact(self):
        """A scalar aggregate whose 8-page block partial could wrap int32
        takes the counted per-page fallback and still equals the oracle."""
        eng = Engine("ssi")
        t = eng.begin()
        for i in range(16):
            eng.write(t, f"k:{i}", 2**30 + i)
        eng.commit(t)
        paged = PagedVersionStore(_mirror_for(eng))
        plan = AggPlan(tuple(f"k:{i}" for i in range(16)),
                       AggOp("sum", "int"))
        kops.reset_launch_stats()
        got = paged.execute(plan, eng.seq)
        assert kops.LAUNCH_STATS["overflow_fallbacks"] == 1
        assert kops.LAUNCH_STATS["pallas_calls"] == 0
        assert got == ChainVersionStore(eng.store).execute(plan, eng.seq)
        assert got == 16 * 2**30 + sum(range(16))
