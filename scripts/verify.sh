#!/usr/bin/env bash
# Repo verification: tier-1 tests + interpret-mode kernel parity checks.
#
#   bash scripts/verify.sh          # tier-1 + kernel parity (fast-ish)
#   bash scripts/verify.sh --bench  # also run the full benchmark suite
#                                   # (writes BENCH_kernels.json)
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== tier-1: pytest =="
python -m pytest -x -q

echo
echo "== interpret-mode kernel parity (version_gather / rss_gather / rss_scan_agg[+grouped]) =="
python - <<'EOF'
import numpy as np, jax, jax.numpy as jnp
from repro.kernels.version_gather.kernel import version_gather
from repro.kernels.version_gather.ref import version_gather_ref
from repro.kernels.rss_gather.kernel import rss_gather
from repro.kernels.rss_gather.ref import rss_gather_ref
from repro.kernels.rss_scan_agg.kernel import rss_scan_agg, rss_scan_agg_grouped
from repro.kernels.rss_scan_agg.ref import (rss_scan_agg_grouped_ref,
                                            rss_scan_agg_ref)

rng = np.random.default_rng(0)
for P, K, E in [(16, 4, 256), (32, 3, 128)]:
    data = jnp.asarray(rng.standard_normal((P, K, E)), jnp.float32)
    ts = jnp.asarray(rng.integers(0, 50, (P, K)), jnp.int32)
    for wm in (0, 13, 49):
        np.testing.assert_array_equal(
            np.asarray(version_gather(data, ts, wm)),
            np.asarray(version_gather_ref(data, ts, wm)))
    for M in (0, 5, 130):
        mem = jnp.asarray(np.sort(rng.choice(np.arange(1, 50), size=min(M, 49),
                                             replace=False)), jnp.int32)
        for floor in (0, 17):   # compressed-snapshot watermark
            np.testing.assert_array_equal(
                np.asarray(rss_gather(data, ts, mem, floor)),
                np.asarray(rss_gather_ref(data, ts, mem, floor)))
for P, K, E in [(16, 4, 32), (32, 3, 16)]:
    idata = np.zeros((P, K, E), np.int32)
    idata[:, :, 0] = rng.integers(-1, 4, (P, K))     # tags incl. TAG_PAD
    idata[:, :, 1] = rng.integers(-99, 99, (P, K))
    its = jnp.asarray(rng.integers(0, 50, (P, K)), np.int32)
    idata = jnp.asarray(idata)
    gid = jnp.asarray(rng.integers(-1, 5, (P, 1)), jnp.int32)
    for M in (0, 7):
        mem = jnp.asarray(np.sort(rng.choice(np.arange(1, 50), size=M,
                                             replace=False)), jnp.int32)
        for floor in (0, 21):
            for tags in [(1, 0, 50), (3, -2, 0)]:
                np.testing.assert_array_equal(
                    np.asarray(rss_scan_agg(idata, its, mem, floor, *tags)),
                    np.asarray(rss_scan_agg_ref(idata, its, mem, floor,
                                                *tags)))
                # grouped variant: per-group accumulator lanes, incl. an
                # empty group (gid never reaches n_groups-1=5) and gid -1
                np.testing.assert_array_equal(
                    np.asarray(rss_scan_agg_grouped(
                        idata, its, gid, mem, floor, *tags, n_groups=6)),
                    np.asarray(rss_scan_agg_grouped_ref(
                        idata, its, gid, mem, floor, *tags, n_groups=6)))
print("kernel parity OK (version_gather, rss_gather+floor, rss_scan_agg "
      "+ grouped; interpret mode)")
EOF

echo
echo "== chunked two-stage parity + whole-batch launch accounting =="
python - <<'EOF'
import numpy as np, jax.numpy as jnp, random
from repro.kernels.rss_scan_agg import ops as kops
from repro.kernels.rss_scan_agg.kernel import (rss_scan_agg_chunked,
                                               rss_scan_agg_grouped,
                                               tree_fold_partials)
from repro.kernels.rss_scan_agg.ops import fold_group_partials
from repro.kernels.rss_scan_agg.ref import rss_scan_agg_chunked_ref

# chunked kernel == segment-sum oracle per chunk; device tree fold ==
# flat-lane host fold (non-divisible G, TAG_PAD, gid -1, empty groups)
rng = np.random.default_rng(1)
for P, K, E in [(24, 3, 16), (1096, 4, 8)]:
    data = np.zeros((P, K, E), np.int32)
    data[:, :, 0] = rng.integers(-1, 4, (P, K))
    data[:, :, 1] = rng.integers(-99, 99, (P, K))
    ts = jnp.asarray(rng.integers(0, 50, (P, K)), np.int32)
    data = jnp.asarray(data)
    for G in (3, 13):
        gid = jnp.asarray(rng.integers(-1, G, (P, 1)), jnp.int32)
        mem = jnp.asarray(np.sort(rng.choice(np.arange(1, 50), size=7,
                                             replace=False)), jnp.int32)
        args = (data, ts, gid, mem, 21, 1, 0, 50)
        chunks = rss_scan_agg_chunked(*args, n_groups=G, rows_per_step=8,
                                      fold_chunks=2)
        np.testing.assert_array_equal(
            np.asarray(chunks),
            np.asarray(rss_scan_agg_chunked_ref(
                *args, n_groups=G, rows_per_step=8, fold_chunks=2)))
        flat = rss_scan_agg_grouped(*args, n_groups=G)
        assert fold_group_partials(chunks) == fold_group_partials(flat)
        np.testing.assert_array_equal(np.asarray(tree_fold_partials(chunks)),
                                      np.asarray(fold_group_partials(chunks)))
print("chunked parity OK (kernel == ref == flat fold; device tree fold)")

# whole-batch plan fusion: N>=4 same-horizon plans -> ONE fused aggregate
# dispatch (and one pallas launch in flat mode, two in chunked)
from repro.mvcc import Engine
from repro.tensorstore import (AggOp, AggPlan, BatchPlan, ChainVersionStore,
                               PagedMirror, PagedVersionStore)
eng = Engine("ssi")
t = eng.begin()
for i in range(32):
    eng.write(t, f"k:{i}", random.Random(i).randrange(-50, 90))
eng.commit(t)
plans = tuple(AggPlan(tuple(f"k:{i + 8 * j}" for i in range(8)),
                      AggOp("sum", "int")) for j in range(4))
oracle = [ChainVersionStore(eng.store).execute(p, eng.seq) for p in plans]
for mode, calls in (("flat", 1), ("chunked", 2)):
    mirror = PagedMirror()
    mirror.catch_up(eng.wal)
    mirror.grouped_mode = mode
    before = dict(mirror.exec_stats)
    kops.reset_launch_stats()
    got = list(PagedVersionStore(mirror).execute(BatchPlan(plans), eng.seq))
    assert got == oracle, (mode, got, oracle)
    assert mirror.exec_stats["agg_dispatches"] - before["agg_dispatches"] \
        == 1, mode
    assert kops.LAUNCH_STATS["dispatches"] == 1, mode
    assert kops.LAUNCH_STATS["pallas_calls"] == calls, \
        (mode, kops.LAUNCH_STATS)
print("plan fusion OK (4-plan batch == oracle; 1 dispatch; "
      "1 launch flat / 2 chunked)")
EOF

echo
echo "== certifier matrix (driver under each policy; fused == oracle) =="
python - <<'EOF'
from repro.core import is_serializable, is_si_history, ssi_accepts
from repro.mvcc import run_multi_node, run_single_node, run_write_skew

for cert in ("conservative-ssi", "commit-order-ssi", "ssn"):
    # HTAP drivers with check_scans=True: every fused plan result is
    # asserted equal to the per-key engine read path (the oracle), and
    # the RSS readers must stay abort-free under every certifier.
    ms = run_single_node(olap_mode="ssi+rss", oltp_clients=4,
                         olap_clients=2, rounds=600, seed=7,
                         olap_scan=True, check_scans=True, certifier=cert)
    assert ms.certifier == cert and ms.oltp_commits > 0
    assert ms.olap_aborts == 0 and ms.olap_wait_rounds == 0, cert
    mm = run_multi_node(olap_mode="ssi+rss", oltp_clients=4,
                        olap_clients=2, rounds=500, seed=7,
                        olap_scan=True, check_scans=True, certifier=cert)
    assert mm.certifier == cert and mm.olap_aborts == 0, cert

    # contended write skew, recorded: zero serializability violations
    m, e = run_write_skew(certifier=cert, contention=0.6, rounds=800,
                          seed=7, record=True)
    assert is_serializable(e.history) and is_si_history(e.history), cert
    if cert != "ssn":   # SSN admits serializable non-SSI histories
        assert ssi_accepts(e.history), cert
    reasons = ";".join(f"{k}={v}" for k, v in
                       sorted(m.by_abort_reason.items())) or "none"
    print(f"certifier OK: {cert:17s} write_skew commits={m.oltp_commits} "
          f"aborts={m.oltp_aborts} [{reasons}]")
print("certifier matrix OK (fused == oracle; RSS abort-/wait-free; "
      "0 serializability violations)")
EOF

echo
echo "== observability (both facades traced; invariants; p50/p99 table) =="
REPRO_TRACE=1 python - <<'EOF'
from repro.mvcc import run_multi_node, run_single_node
from repro.obs import REGISTRY, TRACER

assert TRACER.enabled            # REPRO_TRACE=1 reached the tracer


def table(tag, m):
    print(f"  {tag:28s} {'n':>5s} {'p50_us':>9s} {'p99_us':>10s}")
    rows = [("serve (all plans)", m.serve_latency)]
    rows += sorted(m.serve_latency_by_plan.items())
    rows += [(f"stage:{k}", v) for k, v in
             sorted(m.serve_stage_latency.items())]
    rows.append(("oltp_commit", m.oltp_commit_latency))
    for name, s in rows:
        print(f"  {name:28s} {s['count']:5d} {s['p50_us']:9.1f} "
              f"{s['p99_us']:10.1f}")


def check(m, *, engine_commits):
    steps = (m.olap_scan_steps + m.olap_agg_steps +
             m.olap_multi_agg_steps + m.olap_group_steps)
    by_plan = m.serve_latency_by_plan
    unbatched = sum(v["count"] for k, v in by_plan.items()
                    if k != "BatchPlan")
    fused = by_plan.get("BatchPlan", {"count": 0})["count"]
    # every counted plan step served exactly once (solo or fused)
    assert unbatched == steps - m.olap_batched_plans
    assert fused == m.olap_batch_dispatches
    assert m.serve_latency["count"] == unbatched + fused > 0
    # mirror-layer dispatch accounting == kernel-layer launch accounting
    assert m.olap_agg_dispatches == m.olap_kernel_dispatches > 0
    # engine-layer commits == driver-observed commits; the commit
    # histogram observes successes only
    assert REGISTRY.total("engine_commits") == engine_commits
    assert m.oltp_commit_latency["count"] == engine_commits
    # span trees balanced: opened == closed, stack drained
    assert TRACER.opened == TRACER.closed and TRACER.depth == 0


args = dict(olap_mode="ssi+rss", oltp_clients=3, olap_clients=3,
            rounds=600, seed=13, olap_scan=True, paged_olap=True,
            batch_plans=True)
ms = run_single_node(**args)
check(ms, engine_commits=ms.oltp_commits + ms.olap_commits)
table("single-node (batched)", ms)
mm = run_multi_node(**args, n_replicas=2, route_policy="bounded_staleness")
check(mm, engine_commits=mm.oltp_commits)   # OLAP never hits the primary
table("multi-node N=2 (batched)", mm)
print("  most recent trace tree:")
print("\n".join(f"    {l}" for l in TRACER.render(limit=1).splitlines()))
print("observability OK (latency recorded on both facades; cross-layer "
      "counters consistent; span trees balanced)")
EOF

echo
echo "== materialized aggregates (delta-fold views == oracle on both facades) =="
python - <<'EOF'
import numpy as np
from repro.core.wal import WalRecord
from repro.kernels.rss_scan_agg import ops as kops
from repro.kernels.rss_scan_agg.ref import rss_delta_fold_ref
from repro.mvcc import run_multi_node, run_single_node
from repro.tensorstore import AggOp, MultiAggPlan, PagedMirror

# delta-fold kernel == ref over random dense delta buffers (interpret)
rng = np.random.default_rng(4)
for lp, dp in [(8, 8), (16, 32)]:
    acc = np.zeros((lp, 128), np.int32)
    acc[:, :7] = [0, 0, 0, np.iinfo(np.int32).max,
                  np.iinfo(np.int32).min, 0, 0]
    delta = np.zeros((dp, 128), np.int32)
    delta[:, 0] = rng.integers(-1, lp, dp)         # incl. -1 padding rows
    delta[:, 1] = rng.integers(-99, 99, dp)
    delta[:, 2] = rng.integers(0, 2, dp)
    delta[:, 3] = rng.integers(-99, 99, dp)
    delta[:, 4] = rng.integers(0, 2, dp)
    delta[:, 5] = rng.integers(-50, 50, dp)
    np.testing.assert_array_equal(
        np.asarray(kops.delta_fold(acc, delta, use_kernel=True)),
        np.asarray(rss_delta_fold_ref(acc, delta)))
print("delta_fold parity OK (kernel == ref; interpret mode)")

# registry seam: >=1 view hit AND >=1 clean (gate-miss) fallback, both
# equal to the fused scan
mirror = PagedMirror()
plan = MultiAggPlan(("a", "b", "c"),
                    (AggOp("sum", "int"), AggOp("min", "int")))
mirror.apply(WalRecord(lsn=1, type="commit", txn=1,
                       writes=(("a", 5), ("b", 9), ("c", 2)), seq=1))
mirror.register_view(plan)
mirror.apply(WalRecord(lsn=2, type="commit", txn=2,
                       writes=(("c", 11),), seq=2))
stale = mirror.watermark - 1                 # excludes the queued commit
hit, _ = mirror.execute_with_writers(plan, mirror.watermark,
                                     need_writers=False)
fb, _ = mirror.execute_with_writers(plan, stale, need_writers=False)
assert hit == (25, 5) and fb == (16, 2), (hit, fb)
s = mirror.exec_stats
assert s["view_hits"] >= 1 and s["view_fallbacks"] >= 1, dict(s)

# both facades thread the registry: driver runs with materialize=True
# and check_scans=True assert tile == fused scan == per-key oracle at
# EVERY serve, and the Metrics surface exposes the olap_view_* counters
args = dict(olap_mode="ssi+rss", oltp_clients=3, olap_clients=2,
            rounds=600, seed=5, olap_scan=True, paged_olap=True,
            check_scans=True, materialize=True)
for tag, m in (("single", run_single_node(**args)),
               ("multi", run_multi_node(**args))):
    assert m.olap_view_hits >= 1, (tag, m.olap_view_hits)
    print(f"  {tag:6s} hits={m.olap_view_hits} "
          f"fallbacks={m.olap_view_fallbacks} "
          f"demotions={m.olap_view_demotions}")
print("materialized OK (kernel parity; hit+fallback == fused; both "
      "facades oracle-checked with views on)")
EOF

echo
echo "== session serving (token guarantees; cache == uncached; hit rates) =="
python - <<'EOF'
from repro.mvcc import run_sessions

# Zipf-skewed sticky sessions over a cadence-skewed 2-replica fleet:
# every serve must cover the session's token (read-your-writes +
# monotonic reads) — run_sessions asserts zero violations internally,
# and check_scans asserts every (cached, fused) result == the per-key
# chain oracle.  Cache on vs off must be bit-identical.
args = dict(n_sessions=48, rounds=5, seed=17, n_replicas=2,
            ship_every=2, ship_skew=1, write_fraction=0.2,
            check_scans=True, keep_history=True)
m_off, s_off = run_sessions(resolve_cache=False, batch_plans=False, **args)
m_on, s_on = run_sessions(resolve_cache=True, batch_plans=True, **args)
assert [s.pending for s in s_on] == [s.pending for s in s_off]
for tag, m, ss in (("cache+batch=off", m_off, s_off),
                   ("cache+batch=on", m_on, s_on)):
    assert m.session_token_violations == 0
    assert all(s.session.violations() == 0 for s in ss)
    hits = ";".join(f"{k}={v:.2f}" for k, v in m.cache_hit_rates().items())
    print(f"  {tag:16s} serves={m.session_serves} "
          f"token_ships={m.session_token_ships} "
          f"dispatches={m.olap_batch_dispatches} [{hits}]")
assert m_on.cache_hit_rates()["member"] > 0
assert 0 < m_on.olap_batch_dispatches < m_on.session_serves
print("session serving OK (0 token violations on both runs; cached+"
      "batched == uncached == oracle; caches hit; plans folded)")
EOF

echo
echo "== examples (smoke mode: demos must not rot) =="
for ex in quickstart anomaly_demo paged_snapshot_reads cluster_fanout \
          observability_demo; do
    python "examples/$ex.py" > /dev/null
    echo "example OK: $ex"
done
python examples/htap_train_serve.py --smoke > /dev/null
echo "example OK: htap_train_serve (--smoke)"

echo
echo "== benchmark entry points (--smoke: tiny scale, no BENCH_kernels.json) =="
python -m benchmarks.run --smoke > /dev/null
echo "bench smoke OK (all entry points, incl. scan-vs-fused-agg sweep)"

if [[ "${1:-}" == "--bench" ]]; then
    echo
    echo "== benchmarks (writes BENCH_kernels.json) =="
    python -m benchmarks.run
    echo
    echo "== perf regression gate (fresh run vs committed baseline) =="
    python -m benchmarks.check_regression
fi

echo
echo "verify: all green"
