"""Grouped + compound plans: fused kernels == the per-key chain oracle.

The PR-5 contract of the plan-first executor: `GroupByPlan` (per-group
accumulator lanes, one fused pass -> [groups, 5] tile) and `MultiAggPlan`
(several statistics from one visibility pass) must produce exactly the
per-key chain-walk results at every seam — under randomized replication
lag (batched shipping), RSS state GC, PRoT pins, legacy (unstamped) WAL
records, missing keys, empty groups, duplicate keys across groups, and
both snapshot kinds (compressed RSS snapshots and SI-V watermarks).

Seeded-random stream tests always run; hypothesis widens the search when
available (same harness style as tests/test_rss_scan_agg.py).
"""

import random

import numpy as np
import pytest

from repro.core import PRoTManager, RSSManager, Wal
from repro.core.wal import effective_commit_seq
from repro.mvcc import Engine
from repro.mvcc.store import Store
from repro.tensorstore import (AggOp, ChainVersionStore, GroupByPlan,
                               MultiAggPlan, PagedMirror, PagedVersionStore,
                               ScanPlan, apply_plan, group_by, plan_keys)

KEYS = [f"stock:{i}" for i in range(8)] + ["warehouse:0", "district:0:0",
                                           "order:0:0:0", "order:0:0:1"]
OPS = [AggOp("sum", "int"), AggOp("count", "int"),
       AggOp("count_below", "int", 50), AggOp("count_below", "int", 0),
       AggOp("min", "int"), AggOp("max", "int"),
       AggOp("sum", "total"), AggOp("count", "total"),
       AggOp("min", "total"), AggOp("max", "total")]


def _rand_value(rng, key):
    if key.startswith("district"):
        return {"next_o_id": rng.randrange(40), "ytd": rng.randrange(99)}
    if key.startswith("order"):
        return {"items": [rng.randrange(9) for _ in range(rng.randrange(4))],
                "total": rng.randrange(500)}
    return rng.randrange(-100, 200)


def random_writes_wal(rng, steps=250, *, legacy_prob=0.0):
    """Engine-shaped WAL with committed writesets attached (workload-shaped
    values), deps after reader commits, optional legacy (seq=0) commits."""
    wal = Wal()
    active = []
    tid = 0
    for _ in range(steps):
        act = rng.random()
        if act < 0.35 or not active:
            tid += 1
            wal.log_begin(tid)
            active.append(tid)
        elif act < 0.8:
            t = active.pop(rng.randrange(len(active)))
            seq = 0 if rng.random() < legacy_prob else wal.head_lsn + 1
            writes = [(k, _rand_value(rng, k))
                      for k in rng.sample(KEYS, rng.randint(1, 3))]
            wal.log_commit(t, writes, seq=seq)
            if active and rng.random() < 0.5:
                wal.log_deps(t, sorted(rng.sample(
                    active, rng.randint(1, min(2, len(active))))))
        else:
            t = active.pop(rng.randrange(len(active)))
            wal.log_abort(t)
    return wal


def _rand_plan(rng):
    """A random grouped or compound plan: key groups may be empty, repeat
    keys across groups, and include missing keys."""
    pool = KEYS + ["missing:key"]
    ops = tuple(rng.sample(OPS, rng.randint(1, 4)))
    if rng.random() < 0.5:
        groups = []
        for _ in range(rng.randint(1, 5)):
            groups.append(tuple(rng.sample(pool, rng.randint(0, len(pool)))))
        return GroupByPlan(tuple(groups), ops)
    return MultiAggPlan(tuple(rng.sample(pool, rng.randint(1, len(pool)))),
                        ops)


def check_group_stream(seed, *, gc_prob=0.0, legacy_prob=0.0, pin_prob=0.0,
                       grouped_mode=None):
    """Replay a random stream into RSSManager + paged mirror + chain store
    in randomized batches; at every round, every live snapshot must
    execute random grouped/compound plans identically through the fused
    kernels and the chain oracle (results AND writers).  `grouped_mode`
    pins the mirror's kernel-strategy override (host / flat / chunked) so
    every strategy faces the same stream."""
    rng = random.Random(seed)
    wal = random_writes_wal(rng, legacy_prob=legacy_prob)
    man = RSSManager()
    prot = PRoTManager(man)
    mirror = PagedMirror(slots=64)            # retain everything: parity
    mirror.grouped_mode = grouped_mode
    store = Store()                           # under K-slot pressure is the
    chain = ChainVersionStore(store)          # driver tests' job
    paged = PagedVersionStore(mirror)
    applied_seq = 0
    pruned_floor = 0          # chain reads below this are invalid post-prune
    pins = []
    while man.applied_lsn < wal.head_lsn:
        batch = rng.randint(1, 15)            # lagged, split shipping
        for rec in wal.tail(man.applied_lsn):
            man.apply(rec)
            mirror.apply(rec, gc_floor=prot.gc_floor_seq())
            if rec.type == "commit":
                seq = effective_commit_seq(applied_seq, rec.seq)
                for k, v in rec.writes:
                    store.chain(k).install(seq, rec.txn, v)
                applied_seq = seq
            batch -= 1
            if batch <= 0:
                break
        snap = man.construct()
        for s in [snap, applied_seq,
                  max(applied_seq - 3, pruned_floor)] \
                + [p[1] for p in pins]:
            for _ in range(3):
                plan = _rand_plan(rng)
                want, ww = chain.execute_with_writers(plan, s)
                got, gw = paged.execute_with_writers(plan, s)
                assert want == got, (seed, plan, s, want, got)
                assert ww == gw, (seed, plan, s)
                # ... and both equal the host apply of the scanned values
                keys = plan_keys(plan)
                scanned = chain.execute(ScanPlan(keys), s)
                assert want == apply_plan(scanned, plan), (seed, plan)
        if pin_prob and rng.random() < pin_prob:
            pins.append(prot.acquire())
        if pins and rng.random() < 0.3:
            prot.release(pins.pop(rng.randrange(len(pins)))[0])
        if gc_prob and rng.random() < gc_prob:
            man.gc(keep_lsn=prot.gc_floor(), keep_seq=prot.gc_floor_seq())
            store.prune(prot.gc_floor_seq())
            pruned_floor = max(pruned_floor, prot.gc_floor_seq())


# ------------------------------------------------------------ always-run
@pytest.mark.parametrize("seed", range(6))
def test_grouped_and_compound_equal_chain_oracle(seed):
    check_group_stream(seed)


@pytest.mark.parametrize("seed", range(6))
def test_grouped_equal_oracle_with_gc_and_pins(seed):
    check_group_stream(seed, gc_prob=0.5, pin_prob=0.3)


@pytest.mark.parametrize("seed", range(4))
def test_grouped_equal_oracle_with_legacy_records(seed):
    check_group_stream(seed, legacy_prob=0.3, gc_prob=0.3, pin_prob=0.2)


@pytest.mark.parametrize("mode", ["host", "flat", "chunked"])
@pytest.mark.parametrize("seed", range(2))
def test_grouped_equal_oracle_every_forced_mode(seed, mode):
    """Every kernel strategy — host decode, flat-lane, chunked two-stage —
    must match the chain oracle on the same randomized stream (shape
    dispatch must never be load-bearing for correctness)."""
    check_group_stream(seed, gc_prob=0.3, pin_prob=0.2, grouped_mode=mode)


# ------------------------------------------------------ kernel-level parity
@pytest.mark.parametrize("seed", range(4))
def test_grouped_kernel_matches_ref(seed):
    """Pallas grouped kernel == jnp oracle over random stores, tags,
    floors, members, thresholds, group counts — including TAG_PAD pages,
    gid -1 (no group), group counts that are not sublane multiples, empty
    member sets, and groups no page maps to."""
    import jax.numpy as jnp
    from repro.kernels.rss_scan_agg.kernel import rss_scan_agg_grouped
    from repro.kernels.rss_scan_agg.ref import rss_scan_agg_grouped_ref

    rng = np.random.default_rng(seed)
    for P, K, E in [(8, 3, 8), (16, 4, 32), (64, 4, 16)]:
        data = np.zeros((P, K, E), np.int32)
        data[:, :, 0] = rng.integers(-1, 4, (P, K))     # tags incl. TAG_PAD
        data[:, :, 1] = rng.integers(-100, 100, (P, K))
        ts = rng.integers(0, 60, (P, K)).astype(np.int32)
        for G in (1, 3, 8, 13):
            # gid -1 = no group; G-1 may map to no page (empty group)
            gid = rng.integers(-1, max(G - 1, 1), (P, 1)).astype(np.int32)
            for M in (0, 7, 140):
                mem = np.sort(rng.choice(np.arange(1, 60), size=min(M, 59),
                                         replace=False)).astype(np.int32)
                for floor in (0, 23):
                    for tag_main, tag_alt, thr in [(1, 0, 50), (3, -2, 10)]:
                        args = (jnp.asarray(data), jnp.asarray(ts),
                                jnp.asarray(gid), jnp.asarray(mem), floor,
                                tag_main, tag_alt, thr)
                        np.testing.assert_array_equal(
                            np.asarray(rss_scan_agg_grouped(*args,
                                                            n_groups=G)),
                            np.asarray(rss_scan_agg_grouped_ref(
                                *args, n_groups=G)),
                            err_msg=f"{seed},{P},{G},{M},{floor}")


@pytest.mark.parametrize("seed", range(3))
def test_chunked_kernel_matches_ref_and_flat(seed):
    """Chunked two-stage kernel == its segment-sum oracle per chunk, and
    after the device tree fold == the flat-lane kernel's host fold —
    across TAG_PAD pages, gid -1, empty groups, group counts that don't
    divide the group tile, page counts that don't divide the select
    block, empty/large member sets, and per-group param tiles."""
    import jax.numpy as jnp
    from repro.kernels.rss_scan_agg.kernel import (rss_scan_agg_chunked,
                                                   rss_scan_agg_grouped,
                                                   tree_fold_partials)
    from repro.kernels.rss_scan_agg.ops import fold_group_partials
    from repro.kernels.rss_scan_agg.ref import rss_scan_agg_chunked_ref

    rng = np.random.default_rng(seed)
    # 1096 pages: 18 select rows -> 2 chunks of 2 eight-row steps each
    for P, K, E in [(8, 3, 8), (72, 4, 16), (1096, 4, 8)]:
        data = np.zeros((P, K, E), np.int32)
        data[:, :, 0] = rng.integers(-1, 4, (P, K))     # tags incl. TAG_PAD
        data[:, :, 1] = rng.integers(-100, 100, (P, K))
        ts = rng.integers(0, 60, (P, K)).astype(np.int32)
        for G in (1, 13, 40):
            gid = rng.integers(-1, max(G - 1, 1), (P, 1)).astype(np.int32)
            gprm = np.stack([rng.choice([1, 3], G),
                             rng.choice([0, -2], G),
                             rng.integers(-50, 50, G)], 1).astype(np.int32)
            for M in (0, 7, 140):
                mem = np.sort(rng.choice(np.arange(1, 60), size=min(M, 59),
                                         replace=False)).astype(np.int32)
                for params in ({"tag_main": 1, "tag_alt": 0,
                                "threshold": 50},
                               {"group_params": jnp.asarray(gprm)}):
                    args = (jnp.asarray(data), jnp.asarray(ts),
                            jnp.asarray(gid), jnp.asarray(mem), 23)
                    chunks = rss_scan_agg_chunked(
                        *args, n_groups=G, rows_per_step=8, fold_chunks=2,
                        **params)
                    ref = rss_scan_agg_chunked_ref(
                        *args, n_groups=G, rows_per_step=8, fold_chunks=2,
                        **params)
                    np.testing.assert_array_equal(
                        np.asarray(chunks), np.asarray(ref),
                        err_msg=f"{seed},{P},{G},{M}")
                    # device tree fold == host fold == flat-lane kernel
                    flat = rss_scan_agg_grouped(*args, n_groups=G, **params)
                    assert fold_group_partials(chunks) == \
                        fold_group_partials(flat), (seed, P, G, M)
                    np.testing.assert_array_equal(
                        np.asarray(tree_fold_partials(chunks)),
                        np.asarray(fold_group_partials(chunks)),
                        err_msg=f"{seed},{P},{G},{M}")


def test_grouped_op_empty_groups_and_sentinels():
    """ops-level: a group with no pages folds to count 0 and the fused
    result finalizes min/max to 0 — matching the per-key oracle exactly."""
    eng = Engine("ssi")
    t = eng.begin()
    for i in range(4):
        eng.write(t, f"s:{i}", 10 * (i + 1))
    eng.commit(t)
    mirror = PagedMirror()
    mirror.catch_up(eng.wal)
    plan = GroupByPlan(
        (("s:0", "s:1"), (), ("s:2", "s:3", "missing:x")),
        (AggOp("sum", "int"), AggOp("count", "int"), AggOp("min", "int"),
         AggOp("max", "int")))
    chain = ChainVersionStore(eng.store).execute(plan, eng.seq)
    fused = PagedVersionStore(mirror).execute(plan, eng.seq)
    assert chain == fused
    assert fused[1] == (0, 0, 0, 0)             # empty group
    assert fused[0] == (30, 2, 10, 20)
    assert fused[2] == (70, 3, 0, 40)           # missing key reads as int 0


def test_grouped_duplicate_keys_across_groups():
    """A key in two groups participates in BOTH accumulator lanes (its
    page is gathered once per occurrence, each with its own gid)."""
    eng = Engine("ssi")
    t = eng.begin()
    eng.write(t, "a", 5)
    eng.write(t, "b", 7)
    eng.commit(t)
    mirror = PagedMirror()
    mirror.catch_up(eng.wal)
    plan = GroupByPlan((("a", "b"), ("b",)), (AggOp("sum", "int"),))
    chain = ChainVersionStore(eng.store).execute(plan, eng.seq)
    fused = PagedVersionStore(mirror).execute(plan, eng.seq)
    assert chain == fused == ((12,), (7,))


def test_multi_agg_one_config_per_field_threshold():
    """A compound of ops sharing one (field, threshold) config costs ONE
    fused device pass; distinct thresholds/fields add passes — asserted by
    counting sub-store exports (`jnp_store_for` calls via range_stats)."""
    eng = Engine("ssi")
    t = eng.begin()
    for i in range(6):
        eng.write(t, f"s:{i}", i * 10)
    eng.commit(t)
    mirror = PagedMirror()
    mirror.catch_up(eng.wal)
    paged = PagedVersionStore(mirror)
    keys = tuple(f"s:{i}" for i in range(6))

    def passes(plan):
        # jnp_store_for is called once per execute; kernel passes share it,
        # so count kernel configs through _scalar_raws' config dedup
        from repro.tensorstore.mirror import _op_config
        return len(dict.fromkeys(_op_config(op) for op in plan.ops))

    one = MultiAggPlan(keys, (AggOp("sum", "int"), AggOp("count", "int"),
                              AggOp("min", "int"), AggOp("max", "int")))
    assert passes(one) == 1
    two = MultiAggPlan(keys, (AggOp("count_below", "int", 10),
                              AggOp("count_below", "int", 30)))
    assert passes(two) == 2
    # results still match the oracle either way
    for plan in (one, two):
        assert paged.execute(plan, eng.seq) == \
            ChainVersionStore(eng.store).execute(plan, eng.seq)


def test_group_by_key_fn_builder():
    """`group_by` builds a GroupByPlan from a key-classifier in
    first-appearance order and returns the labels."""
    keys = ["customer:0:0:0", "customer:0:1:0", "customer:0:0:1",
            "customer:1:0:0"]
    labels, plan = group_by(keys, lambda k: k.split(":")[1],
                            [AggOp("sum", "int")])
    assert labels == ("0", "1")
    assert plan.key_groups == (
        ("customer:0:0:0", "customer:0:1:0", "customer:0:0:1"),
        ("customer:1:0:0",))
    assert plan_keys(plan) == tuple(keys[:3] + keys[3:])


# ------------------------------------------------------------ engine seams
class TestEnginePlanSeam:
    def test_group_plan_records_flat_read_set(self):
        eng = Engine("ssi", record=True)
        t0 = eng.begin()
        eng.write(t0, "a", 7)
        eng.write(t0, "b", 3)
        eng.commit(t0)
        t = eng.begin(read_only=True, skip_siread=True)
        plan = GroupByPlan((("a",), ("b", "c")), (AggOp("sum", "int"),))
        got = eng.execute(t, plan)
        assert got == ((7,), (3,))
        assert t.reads == {"a": t0.tid, "b": t0.tid, "c": 0}
        reads = [op for op in eng.history.ops
                 if op.kind == "r" and op.txn == t.tid]
        assert len(reads) == 3

    def test_ssi_tracked_group_plan_falls_back_to_per_key_reads(self):
        eng = Engine("ssi")
        t = eng.begin(read_only=True)
        eng.execute(t, MultiAggPlan(("a", "b"), (AggOp("count", "int"),)))
        assert t.tid in eng.siread.get("a", set())
        assert t.tid in eng.siread.get("b", set())

    def test_group_plan_sees_own_writes(self):
        eng = Engine("si")
        t = eng.begin()
        eng.write(t, "k1", 42)
        plan = GroupByPlan((("k0", "k1"), ("k1",)),
                           (AggOp("sum", "int"), AggOp("max", "int")))
        assert eng.execute(t, plan) == ((42, 42), (42, 42))


# ------------------------------------------------------------ facade seams
class TestFacadePlanSeam:
    def test_driver_serves_group_and_multi_plans_checked(self):
        from repro.mvcc.driver import run_single_node
        m = run_single_node(olap_mode="ssi+rss", oltp_clients=4,
                            olap_clients=2, rounds=1500, seed=3,
                            olap_scan=True, paged_olap=True,
                            check_scans=True)
        assert m.olap_group_steps > 0       # GroupByPlan served + checked
        assert m.olap_multi_agg_steps > 0   # MultiAggPlan served + checked
        assert m.olap_agg_steps > 0 and m.olap_scan_steps > 0

    def test_multi_node_serves_group_and_multi_plans_checked(self):
        from repro.mvcc.driver import run_multi_node
        m = run_multi_node(olap_mode="ssi+rss", oltp_clients=4,
                           olap_clients=2, rounds=1500, seed=3,
                           olap_scan=True, paged_olap=True,
                           check_scans=True, n_replicas=2)
        assert m.olap_group_steps > 0
        assert m.olap_multi_agg_steps > 0

    def test_reserved_key_families_raise_dense_hit_rate(self):
        """Page-range locality: with key families reserved contiguously
        (the driver default), dense plans slice instead of gather — the
        fast-path hit rate is recorded and high."""
        from repro.mvcc.driver import run_single_node
        m = run_single_node(olap_mode="ssi+rss", oltp_clients=4,
                            olap_clients=2, rounds=1500, seed=3,
                            olap_scan=True, paged_olap=True)
        assert m.olap_dense_range_hits > 0
        # stock/customer family plans all slice; only order-key plans
        # (dynamic allocation) may gather
        assert m.dense_range_hit_rate() > 0.5

    def test_unreserved_mirror_mostly_gathers(self):
        """Counter-check: WAL-order page allocation scatters key families,
        so the same workload shape without reservation mostly gathers."""
        from repro.mvcc.htap import SingleNodeHTAP
        from repro.mvcc.workload import Scale, load_initial
        from repro.tensorstore import AggPlan

        sc = Scale()
        htap = SingleNodeHTAP("ssi+rss", paged=True)   # no reserve_keys
        rng = random.Random(0)
        keys = sc.all_stock_keys()
        shuffled = list(keys)
        rng.shuffle(shuffled)
        t = htap.engine.begin()
        for k in shuffled:                  # commit in shuffled order
            htap.engine.write(t, k, rng.randrange(100))
        htap.engine.commit(t)
        htap.refresh_rss()
        r = htap.olap_begin()
        htap.olap_execute(r, AggPlan(tuple(keys), AggOp("sum", "int")))
        assert htap.mirror.range_stats["gather"] > 0
        assert htap.mirror.range_stats["dense"] == 0


# ------------------------------------------------------------- hypothesis
try:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 10_000), gc=st.booleans(), legacy=st.booleans())
    def test_grouped_equal_oracle_hypothesis(seed, gc, legacy):
        check_group_stream(seed, gc_prob=0.5 if gc else 0.0,
                           legacy_prob=0.3 if legacy else 0.0, pin_prob=0.2)
except ImportError:                      # pragma: no cover
    pass
