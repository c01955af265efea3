"""Materialized aggregates: incremental tiles == the chain oracle.

The tentpole contract: a registered plan's live accumulator tile —
advanced by commit-delta folds, demoted per-lane when a min/max bound
retracts, gated on snapshot membership — must be indistinguishable from
the fused-scan path and the per-key chain walk at EVERY serve, under
randomized replication lag, RSS state GC, PRoT pins, WAL truncation
below the watermark, legacy (unstamped) records, late registration, and
full reseeds.  Views may fall back (gate miss) or degrade (overflow,
fold-order violation) — they may never serve a stale or wrong result.

Harness style follows tests/test_group_agg.py: seeded-random streams
against RSSManager + PagedMirror + ChainVersionStore.
"""

import random

import numpy as np
import pytest

from repro.core import PRoTManager, RSSManager, Wal
from repro.core.wal import WalRecord, effective_commit_seq
from repro.mvcc.store import Store
from repro.tensorstore import (AggOp, ChainVersionStore, GroupByPlan,
                               MultiAggPlan, PagedMirror, PagedVersionStore)
from repro.tensorstore.materialized import MAX_CONTRIB

STOCK = [f"stock:{i}" for i in range(8)]
ORDERS = ["order:0:0:0", "order:0:0:1"]
KEYS = STOCK + ["warehouse:0", "district:0:0"] + ORDERS

# statically-fingerprinted plans a session would register (all seven
# fold lanes exercised: additive, thresholded, and min/max demotion)
PLAN_MULTI = MultiAggPlan(
    tuple(STOCK), (AggOp("sum", "int"), AggOp("count", "int"),
                   AggOp("min", "int"), AggOp("max", "int"),
                   AggOp("count_below", "int", 50),
                   AggOp("count_above", "int", 90),
                   AggOp("sum_below", "int", 100)))
PLAN_GROUP = GroupByPlan(
    (tuple(STOCK[:4]), tuple(STOCK[4:])),
    (AggOp("sum", "int"), AggOp("max", "int")))
PLAN_TOTAL = MultiAggPlan(
    tuple(ORDERS), (AggOp("sum", "total"), AggOp("count", "total")))
PLANS = (PLAN_MULTI, PLAN_GROUP, PLAN_TOTAL)


def _rand_value(rng, key):
    if key.startswith("district"):
        return {"next_o_id": rng.randrange(40), "ytd": rng.randrange(99)}
    if key.startswith("order"):
        return {"items": [rng.randrange(9) for _ in range(rng.randrange(4))],
                "total": rng.randrange(500)}
    return rng.randrange(-100, 200)


def random_writes_wal(rng, steps=220, *, legacy_prob=0.0):
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
            wal.log_abort(active.pop(rng.randrange(len(active))))
    return wal


def _check_tile_matches_shadow(view):
    """Device tile == int64 host shadow, lane for lane (post flush and
    demotion) — the kernel-fold vs host-fold parity seam."""
    if view.degraded:
        return
    rows = view.serve_rows()
    assert rows == [[int(x) for x in r] for r in view.shadow], \
        (rows, view.shadow)


def check_view_stream(seed, *, gc_prob=0.0, pin_prob=0.0,
                      truncate_prob=0.0, legacy_prob=0.0,
                      reseed_prob=0.0, late_register=False,
                      use_kernel=False):
    """Replay a random commit stream; every live snapshot must execute
    the registered plans identically through the materialized registry
    (hit, fallback, or degraded) and the chain oracle.  Returns the
    mirror's exec stats for hit/fallback assertions."""
    rng = random.Random(seed)
    wal = random_writes_wal(rng, legacy_prob=legacy_prob)
    man = RSSManager()
    prot = PRoTManager(man)
    mirror = PagedMirror(slots=64)
    store = Store()
    chain = ChainVersionStore(store)
    paged = PagedVersionStore(mirror)
    if not late_register:
        for p in PLANS:
            mirror.register_view(p, use_kernel=use_kernel)
    applied_seq = 0
    pruned_floor = 0
    registered = not late_register
    pins = []
    rounds = 0
    while man.applied_lsn < wal.head_lsn:
        batch = rng.randint(1, 15)
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
        rounds += 1
        if late_register and not registered and rounds >= 4:
            for p in PLANS:
                mirror.register_view(p, use_kernel=use_kernel)
            registered = True
        snap = man.construct()
        mirror.advance_views(snap)            # the facade's refresh step
        # fresh snapshot first (the hit path), stale/pinned after (the
        # fallback path) — every serve must equal the chain oracle
        stale = [applied_seq, max(applied_seq - 3, pruned_floor)] \
            + [p[1] for p in pins]
        for s in [snap] + stale:
            for plan in PLANS:
                want = chain.execute(plan, s)
                got = paged.execute(plan, s)
                assert want == got, (seed, plan, s, want, got)
        if registered:
            for view in mirror.views.values():
                _check_tile_matches_shadow(view)
        if pin_prob and rng.random() < pin_prob:
            pins.append(prot.acquire())
        if pins and rng.random() < 0.3:
            prot.release(pins.pop(rng.randrange(len(pins)))[0])
        if gc_prob and rng.random() < gc_prob:
            man.gc(keep_lsn=prot.gc_floor(), keep_seq=prot.gc_floor_seq())
            mirror.gc_views(prot.gc_floor_seq())
            store.prune(prot.gc_floor_seq())
            pruned_floor = max(pruned_floor, prot.gc_floor_seq())
        if truncate_prob and rng.random() < truncate_prob:
            # recycle the fully-applied WAL prefix (below the watermark);
            # views must keep serving from incremental state
            wal.truncate(min(man.applied_lsn, mirror.applied_lsn))
        if reseed_prob and rng.random() < reseed_prob:
            mirror.reseed_views()
    return dict(mirror.exec_stats)


# ------------------------------------------------------------ always-run
@pytest.mark.parametrize("seed", range(3))
def test_views_equal_chain_oracle_stream(seed):
    stats = check_view_stream(seed)
    assert stats["view_hits"] > 0, stats


@pytest.mark.parametrize("seed", range(3))
def test_views_survive_gc_pins_and_truncation(seed):
    stats = check_view_stream(seed, gc_prob=0.5, pin_prob=0.3,
                              truncate_prob=0.4)
    assert stats["view_hits"] > 0, stats
    assert stats["view_fallbacks"] > 0, stats     # stale serves fell back


@pytest.mark.parametrize("seed", range(2))
def test_views_with_legacy_records(seed):
    check_view_stream(seed, legacy_prob=0.3, gc_prob=0.3, pin_prob=0.2,
                      truncate_prob=0.3)


@pytest.mark.parametrize("seed", range(2))
def test_views_late_registration_and_reseed(seed):
    stats = check_view_stream(seed, late_register=True, reseed_prob=0.2,
                              gc_prob=0.3)
    assert stats["view_hits"] > 0, stats


def test_views_kernel_fold_parity_stream():
    """One full stream through the REAL delta-fold kernel (interpret on
    CPU): tile rows must match the int64 host shadow at every round —
    covered inline by _check_tile_matches_shadow."""
    stats = check_view_stream(0, use_kernel=True)
    assert stats["view_hits"] > 0, stats


# ------------------------------------------------------------ unit seams
def _mirror_with_view(values, *, plan=None):
    mirror = PagedMirror(slots=64)
    plan = plan or MultiAggPlan(tuple(sorted(values)),
                                (AggOp("sum", "int"), AggOp("min", "int")))
    mirror.apply(WalRecord(lsn=1, type="commit", txn=1,
                           writes=tuple(values.items()), seq=1))
    view = mirror.register_view(plan, use_kernel=False)
    return mirror, view, plan


def test_overflow_degrades_to_clean_fallback():
    vals = {"a": 1, "b": 2}
    mirror, view, plan = _mirror_with_view(vals)
    mirror.apply(WalRecord(lsn=2, type="commit", txn=2,
                           writes=(("a", MAX_CONTRIB + 1),), seq=2))
    mirror.advance_views(mirror.watermark)
    assert view.degraded
    # the degraded view falls back to the fused scan — still exact
    got, _ = mirror.execute_with_writers(plan, mirror.watermark,
                                         need_writers=False)
    assert got == (MAX_CONTRIB + 1 + 2, 2)
    assert mirror.exec_stats["view_fallbacks"] > 0


def test_bulk_record_folds_in_bounded_buffers(monkeypatch):
    """A record writing more keys than FLUSH_ROWS (a bulk load) folds in
    delta buffers of at most FLUSH_ROWS rows: the int32 bound assumes
    it, and the fold kernel's compile time grows with the buffer."""
    from repro.kernels.rss_scan_agg import ops as kops
    from repro.tensorstore.materialized import FLUSH_ROWS

    keys = [f"k:{i:04d}" for i in range(2 * FLUSH_ROWS + 88)]
    mirror, view, plan = _mirror_with_view({k: 1 for k in keys})
    rows = []
    fold = kops.delta_fold
    monkeypatch.setattr(kops, "delta_fold", lambda acc, delta, **kw: (
        rows.append(delta.shape[0]) or fold(acc, delta, **kw)))
    mirror.apply(WalRecord(lsn=2, type="commit", txn=2,
                           writes=tuple((k, i) for i, k in enumerate(keys)),
                           seq=2))
    mirror.advance_views(mirror.watermark)
    got, _ = mirror.execute_with_writers(plan, mirror.watermark,
                                         need_writers=False)
    assert got == (sum(range(len(keys))), 0)
    assert len(rows) == 3 and max(rows) <= FLUSH_ROWS, rows


def test_out_of_order_same_key_fold_degrades():
    """A same-key fold below an already-folded seq would retract the
    newer version — the view must refuse (degrade), never serve it."""
    _, view, _ = _mirror_with_view({"a": 1, "b": 2})
    view.on_commit(WalRecord(lsn=2, type="commit", txn=2,
                             writes=(("a", 10),), seq=5), 5)
    assert not view.degraded
    view.on_commit(WalRecord(lsn=3, type="commit", txn=3,
                             writes=(("a", 7),), seq=4), 4)
    assert view.degraded


def test_demotion_recomputes_min_after_bound_retraction():
    vals = {"a": 3, "b": 8, "c": 5}
    mirror, view, plan = _mirror_with_view(vals)
    # overwrite the attained min: the min lane goes dirty and must be
    # recomputed by a partial rescan at serve time
    mirror.apply(WalRecord(lsn=2, type="commit", txn=2,
                           writes=(("a", 9),), seq=2))
    got, _ = mirror.execute_with_writers(plan, mirror.watermark,
                                         need_writers=False)
    assert got == (9 + 8 + 5, 5)
    assert mirror.exec_stats["view_hits"] == 1
    assert mirror.exec_stats["view_demotions"] >= 1


def test_duplicate_keys_in_group_rejected():
    mirror = PagedMirror(slots=64)
    with pytest.raises(ValueError):
        mirror.register_view(MultiAggPlan(("a", "a"),
                                          (AggOp("sum", "int"),)))


def test_registry_is_idempotent_by_fingerprint():
    vals = {"a": 1}
    mirror, view, plan = _mirror_with_view(vals)
    twin = MultiAggPlan(tuple(sorted(vals)),
                        (AggOp("sum", "int"), AggOp("min", "int")))
    assert mirror.register_view(twin) is view     # equal plan, same view
    assert len(mirror.views) == 1


# ------------------------------------------------------- facade threading
def test_single_node_facade_serves_and_counts():
    from repro.mvcc.driver import run_single_node
    m = run_single_node(olap_mode="ssi+rss", oltp_clients=3, olap_clients=2,
                        rounds=600, olap_scan=True, paged_olap=True,
                        check_scans=True, materialize=True, seed=5)
    assert m.olap_view_hits > 0, m
    assert m.olap_view_fallbacks >= 0


def test_replica_delta_ship_advances_views():
    from repro.mvcc.driver import run_multi_node
    m = run_multi_node(olap_mode="ssi+rss", oltp_clients=3, olap_clients=2,
                       rounds=600, olap_scan=True, paged_olap=True,
                       check_scans=True, materialize=True, seed=5)
    assert m.olap_view_hits > 0, m
