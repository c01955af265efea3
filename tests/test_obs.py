"""Observability layer: registry units, stats-view compatibility, span
tracing, the LAUNCH_STATS cross-run-leakage regression, single-node vs
N=1-cluster harvest parity, and cross-layer counter invariants under
randomized driver runs."""

import json

import pytest

from repro.kernels.rss_scan_agg import ops as kops
from repro.mvcc import run_multi_node, run_single_node
from repro.obs import (REGISTRY, TRACER, CounterList, LabeledCounterMap,
                       MetricRegistry, StatsView, set_timing, tick,
                       timing_enabled, tock)


# --------------------------------------------------------------- registry
def test_counter_gauge_identity_and_labels():
    reg = MetricRegistry()
    c1 = reg.counter("x_total", who="a")
    c2 = reg.counter("x_total", who="a")
    c3 = reg.counter("x_total", who="b")
    assert c1 is c2 and c1 is not c3          # (name, labels) keys series
    c1.inc()
    c1.inc(4)
    c3.inc(2)
    assert c1.value == 5
    assert reg.total("x_total") == 7          # family total across labels
    assert reg.total("x_total", who="b") == 2
    g = reg.gauge("peak")
    g.track_max(3)
    g.track_max(1)
    assert g.value == 3
    with pytest.raises(AssertionError):       # kind mismatch is a bug
        reg.gauge("x_total", who="a")


def test_histogram_percentiles_bounded_memory():
    reg = MetricRegistry()
    h = reg.histogram("lat_seconds")
    for _ in range(100):
        h.observe(1e-3)
    for _ in range(10):
        h.observe(1e-1)
    assert h.count == 110
    # p50 lands inside the bucket covering 1e-3 (log-spaced, 4/decade)
    assert 5e-4 <= h.percentile(0.50) <= 1e-3
    assert 5e-2 <= h.percentile(0.99) <= 2e-1
    assert h.percentile(0.0) >= 0.0
    s = h.snap()
    assert s["count"] == 110 and s["p50_us"] <= 1000.0
    # overflow bucket clamps to the last boundary instead of growing state
    h.observe(1e6)
    assert h.percentile(1.0) == h.bounds[-1]
    assert len(h.counts) == len(h.bounds) + 1  # fixed, sample-count-free


def test_registry_snapshot_export_reset():
    reg = MetricRegistry()
    reg.counter("a_total", k="v").inc(3)
    reg.histogram("b_seconds").observe(2e-3)
    snap = reg.snapshot()
    assert snap["counters"]['a_total{k="v"}'] == 3
    assert snap["histograms"]["b_seconds"]["count"] == 1
    assert json.loads(reg.to_json())["counters"] == snap["counters"]
    prom = reg.render_prometheus()
    assert "# TYPE a_total counter" in prom
    assert 'a_total{k="v"} 3' in prom
    assert "b_seconds_count 1" in prom and "le=" in prom
    pre = reg.reset()                        # atomic: snapshot THEN zero
    assert pre["counters"]['a_total{k="v"}'] == 3
    assert reg.counter("a_total", k="v").value == 0
    assert reg.hist_summary("b_seconds")["count"] == 0


def test_hist_summary_merges_label_sets():
    reg = MetricRegistry()
    reg.histogram("s_seconds", plan="A").observe(1e-3)
    reg.histogram("s_seconds", plan="B").observe(1e-3)
    reg.histogram("s_seconds", plan="B").observe(1e-2)
    assert reg.hist_summary("s_seconds")["count"] == 3
    assert reg.hist_summary("s_seconds", plan="B")["count"] == 2
    grouped = reg.hist_group("s_seconds", "plan")
    assert grouped["A"]["count"] == 1 and grouped["B"]["count"] == 2


def test_stats_view_is_a_dict_shaped_counter_view():
    reg = MetricRegistry()
    sv = StatsView(reg, "eng", ("commits", "aborts"),
                   labels={"engine": "e1"},
                   sub={"by_reason": LabeledCounterMap(
                       reg, "eng_by_reason", "reason",
                       labels={"engine": "e1"})})
    sv["commits"] += 2
    sv["aborts"] = 5
    sv["by_reason"]["pivot"] = sv["by_reason"].get("pivot", 0) + 1
    assert sv["commits"] == 2 and sv["aborts"] == 5
    assert sv == {"commits": 2, "aborts": 5, "by_reason": {"pivot": 1}}
    assert dict(sv)["commits"] == 2
    assert reg.counter("eng_commits", engine="e1").value == 2
    assert reg.total("eng_by_reason") == 1
    # zero-valued open keys stay invisible (ad-hoc-dict semantics)
    sv["by_reason"]["ww"] = 0
    assert dict(sv["by_reason"]) == {"pivot": 1}
    # atomic reset returns the pre-reset dict; registrations survive
    pre = sv.reset()
    assert pre == {"commits": 2, "aborts": 5}
    assert sv["commits"] == 0 and sv == {"commits": 0, "aborts": 0,
                                         "by_reason": {"pivot": 1}}
    with pytest.raises(TypeError):
        del sv["commits"]


def test_counter_list_view():
    reg = MetricRegistry()
    served = CounterList(reg, "served", 3, labels={"cluster": "c1"})
    served[1] += 2
    served[2] = 7
    assert list(served) == [0, 2, 7] and served == [0, 2, 7]
    assert served[0:2] == [0, 2] and len(served) == 3
    assert reg.counter("served", cluster="c1", replica="2").value == 7


def test_tick_tock_stubbable():
    reg = MetricRegistry()
    h = reg.histogram("t_seconds")
    assert timing_enabled()
    t0 = tick()
    tock(h, t0)
    assert h.count == 1
    set_timing(False)
    try:
        assert tick() == 0.0           # no perf_counter call when stubbed
        tock(h, tick())
        assert h.count == 1
    finally:
        set_timing(True)


# ----------------------------------------------------------------- tracer
def test_tracer_disabled_is_shared_null_context():
    TRACER.set_enabled(False)
    try:
        s1, s2 = TRACER.span("a"), TRACER.span("b", x=1)
        assert s1 is s2                # nothing allocated when off
        with s1:
            assert TRACER.depth == 0
    finally:
        TRACER.set_enabled(None)


def test_tracer_capture_nesting_balance_render():
    TRACER.set_enabled(True)
    TRACER.clear()
    opened0, closed0 = TRACER.opened, TRACER.closed
    try:
        with TRACER.span("root", kind="serve") as root:
            with TRACER.span("child"):
                TRACER.annotate(mode="flat")
            with TRACER.span("child2"):
                pass
        assert TRACER.depth == 0
        assert TRACER.opened - opened0 == 3 == TRACER.closed - closed0
        assert [c.name for c in root.children] == ["child", "child2"]
        assert root.children[0].labels == {"mode": "flat"}
        text = TRACER.render()
        assert "root" in text and "child2" in text and "us" in text
        assert list(TRACER.traces)[-1] is root     # roots land in the deque
    finally:
        TRACER.set_enabled(None)
        TRACER.clear()


def test_tracer_env_default(monkeypatch):
    TRACER.set_enabled(None)
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    assert not TRACER.enabled                      # off by default
    monkeypatch.setenv("REPRO_TRACE", "1")
    assert TRACER.enabled
    monkeypatch.setenv("REPRO_TRACE", "off")
    assert not TRACER.enabled


def test_tracer_survives_exception_balanced():
    TRACER.set_enabled(True)
    TRACER.clear()
    opened0, closed0 = TRACER.opened, TRACER.closed
    try:
        with pytest.raises(ValueError):
            with TRACER.span("will_raise"):
                with TRACER.span("inner"):
                    raise ValueError("boom")
        assert TRACER.depth == 0
        assert TRACER.opened - opened0 == 2 == TRACER.closed - closed0
    finally:
        TRACER.set_enabled(None)
        TRACER.clear()


# ------------------------------------- LAUNCH_STATS cross-run regression
def test_launch_stats_is_registry_backed_with_atomic_reset():
    kops.reset_launch_stats()
    kops.LAUNCH_STATS["dispatches"] += 3
    kops.LAUNCH_STATS["host"] += 1
    assert REGISTRY.total("kernel_launch_dispatches") >= 3
    snap = kops.reset_launch_stats()
    assert snap["dispatches"] == 3 and snap["host"] == 1
    assert dict(kops.LAUNCH_STATS) == {k: 0 for k in kops.LAUNCH_STATS}


def test_back_to_back_driver_runs_start_from_zero():
    """The LAUNCH_STATS global-dict hazard: a second run must not inherit
    the first run's kernel launch accounting (or any other layer's)."""
    args = dict(olap_mode="ssi+rss", oltp_clients=2, olap_clients=2,
                rounds=250, seed=11, olap_scan=True, paged_olap=True)
    m1 = run_single_node(**args)
    m2 = run_single_node(**args)
    assert m1.olap_kernel_dispatches > 0
    # identical runs harvest identical counters — leakage would double m2
    for f in ("olap_kernel_dispatches", "olap_kernel_pallas_calls",
              "olap_agg_dispatches", "olap_dense_range_hits",
              "olap_mode_flat", "olap_mode_chunked", "olap_mode_host"):
        assert getattr(m1, f) == getattr(m2, f), f
    assert m1.serve_latency["count"] == m2.serve_latency["count"]
    assert m1.oltp_commit_latency["count"] == m2.oltp_commit_latency["count"]


# -------------------------------- single-node vs N=1 cluster aggregation
def test_single_vs_n1_cluster_harvest_parity():
    """The multi-node Metrics harvest summed per-replica stats while
    single-node assigned — both now snapshot the same registry totals.
    Pin: for BOTH architectures the harvested mirror/kernel counters equal
    the per-instance view values, and the N=1 cluster run serves every
    plan step it counts."""
    ms = run_single_node(olap_mode="ssi+rss", oltp_clients=2,
                         olap_clients=2, rounds=250, seed=5,
                         olap_scan=True, paged_olap=True)
    assert ms.olap_agg_dispatches == ms.olap_kernel_dispatches > 0
    mm = run_multi_node(olap_mode="ssi+rss", oltp_clients=2,
                        olap_clients=2, rounds=250, seed=5,
                        olap_scan=True, paged_olap=True, n_replicas=1)
    assert mm.olap_agg_dispatches == mm.olap_kernel_dispatches > 0
    steps = (mm.olap_scan_steps + mm.olap_agg_steps +
             mm.olap_multi_agg_steps + mm.olap_group_steps)
    assert mm.serve_latency["count"] == steps > 0
    assert mm.olap_dense_range_hits + mm.olap_dense_range_misses > 0


def test_multi_node_totals_equal_per_replica_sum():
    m = run_multi_node(olap_mode="ssi+rss", oltp_clients=2, olap_clients=3,
                       rounds=300, seed=9, olap_scan=True, paged_olap=True,
                       n_replicas=3, route_policy="round_robin")
    # registry family totals == hand-summed per-replica view values (the
    # pre-registry multi-node harvest, kept as the oracle)
    assert m.olap_agg_dispatches == \
        REGISTRY.total("mirror_exec_agg_dispatches")
    assert m.olap_dense_range_hits == REGISTRY.total("mirror_range_dense")
    assert sum(m.olap_served_by) == REGISTRY.total("cluster_served") > 0


# --------------------------------------------- cross-layer invariants
def _serve_invariants(m, batching: bool):
    steps = (m.olap_scan_steps + m.olap_agg_steps +
             m.olap_multi_agg_steps + m.olap_group_steps)
    by_plan = m.serve_latency_by_plan
    unbatched = sum(v["count"] for k, v in by_plan.items()
                    if k != "BatchPlan")
    fused = by_plan.get("BatchPlan", {"count": 0})["count"]
    assert m.serve_latency["count"] == unbatched + fused
    if not batching:
        assert fused == 0 and unbatched == steps
    else:
        # every counted plan step is served exactly once: individually, or
        # as a member of a fused BatchPlan dispatch
        assert unbatched == steps - m.olap_batched_plans
        assert fused == m.olap_batch_dispatches


@pytest.mark.parametrize("batching", [False, True])
def test_cross_layer_invariants_single_node(batching):
    TRACER.set_enabled(True)
    try:
        m = run_single_node(olap_mode="ssi+rss", oltp_clients=3,
                            olap_clients=3, rounds=400, seed=21,
                            olap_scan=True, paged_olap=True,
                            batch_plans=batching)
    finally:
        TRACER.set_enabled(None)
    _serve_invariants(m, batching)
    # mirror-layer grouped dispatches == kernel-layer dispatch accounting
    assert m.olap_agg_dispatches == m.olap_kernel_dispatches
    # engine commits (post-reset window) == driver-counted commits: every
    # OLTP and OLAP commit goes through Engine.commit on this facade
    assert REGISTRY.total("engine_commits") == \
        m.oltp_commits + m.olap_commits
    # commit latency histogram observes successful commits only
    assert m.oltp_commit_latency["count"] == \
        m.oltp_commits + m.olap_commits
    # engine-recorded aborts vs driver-observed: the driver may not yet
    # have stepped a client whose txn the engine aborted mid-flight, so
    # engine >= driver, within one in-flight txn per client
    eng_aborts = REGISTRY.total("engine_aborts")
    drv_aborts = sum(m.by_abort_reason.values())
    assert drv_aborts <= eng_aborts <= drv_aborts + 6
    # per-reason series sum to the total
    assert REGISTRY.total("engine_aborts_by_reason") == eng_aborts
    # span trees balanced: every opened span closed, stack drained
    assert TRACER.opened == TRACER.closed and TRACER.depth == 0
    # stage histograms cover the serve path: every serve resolved
    # visibility at least once
    assert m.serve_stage_latency["resolve"]["count"] >= \
        m.serve_latency["count"]


@pytest.mark.parametrize("batching", [False, True])
def test_cross_layer_invariants_multi_node(batching):
    TRACER.set_enabled(True)
    try:
        m = run_multi_node(olap_mode="ssi+rss", oltp_clients=3,
                           olap_clients=3, rounds=400, seed=22,
                           olap_scan=True, paged_olap=True, n_replicas=2,
                           route_policy="bounded_staleness",
                           batch_plans=batching)
    finally:
        TRACER.set_enabled(None)
    _serve_invariants(m, batching)
    assert m.olap_agg_dispatches == m.olap_kernel_dispatches
    # multi-node OLAP commits never touch the primary engine
    assert REGISTRY.total("engine_commits") == m.oltp_commits
    assert m.oltp_commit_latency["count"] == m.oltp_commits
    assert TRACER.opened == TRACER.closed and TRACER.depth == 0
    # the route stage is the cluster acquire path
    assert m.serve_stage_latency["route"]["count"] == \
        REGISTRY.total("cluster_acquires") > 0


# ------------------------------------------------- the span() primitive
def test_span_observes_hist_and_records_tree_node():
    from repro.obs import span
    reg = MetricRegistry()
    outer_h, inner_h = reg.histogram("a_seconds"), reg.histogram("b_seconds")
    TRACER.set_enabled(True)
    TRACER.clear()
    try:
        with span("outer", outer_h, plan="AggPlan") as root:
            with span("inner", inner_h):
                TRACER.annotate(mode="flat")
            with span("untimed"):
                pass
        assert outer_h.count == inner_h.count == 1
        assert outer_h.total >= inner_h.total > 0
        assert root.name == "outer" and root.labels == {"plan": "AggPlan"}
        assert [c.name for c in root.children] == ["inner", "untimed"]
        assert root.children[0].labels == {"mode": "flat"}
        # an exception leaves the histogram unobserved (an aborted commit
        # is no commit) and the tree balanced
        with pytest.raises(ValueError):
            with span("outer", outer_h):
                raise ValueError("abort")
        assert outer_h.count == 1 and TRACER.depth == 0
        assert TRACER.opened == TRACER.closed
    finally:
        TRACER.set_enabled(None)
        TRACER.clear()


def test_span_times_when_tracing_is_off_and_not_when_stubbed():
    from repro.obs import span
    reg = MetricRegistry()
    h = reg.histogram("c_seconds")
    TRACER.set_enabled(False)
    try:
        with span("stage", h, k=1) as node:
            assert TRACER.depth == 0
        assert h.count == 1 and node is not None
        set_timing(False)
        try:
            with span("stage", h):
                pass
        finally:
            set_timing(True)
        assert h.count == 1
    finally:
        TRACER.set_enabled(None)


def _host_events(log_dir):
    import glob
    import os

    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for ln in plane.lines for e in ln.events]


def _covers(outer, inner) -> bool:
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_span_enters_the_profiler_trace_only_when_it_runs(tmp_path):
    """Outside a profiler session a span holds no annotation; inside one,
    `repro:<name>` events land on the host plane, nested as the spans
    were and inside the caller's own annotation, over a whole serve and
    commit path."""
    import jax

    from repro.obs import PROFILER_PREFIX, span
    from repro.obs.trace import _Annotation
    assert not _Annotation.is_enabled()
    assert span("stage")._ann is None
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench:phase"):
            with span("outer"):
                with span("inner", REGISTRY.histogram("probe_seconds")):
                    pass
            run_single_node(olap_mode="ssi+rss", oltp_clients=2,
                            olap_clients=2, rounds=40, seed=5,
                            olap_scan=True, paged_olap=True)
    finally:
        jax.profiler.stop_trace()
    evs = _host_events(str(tmp_path))
    phase = [e for e in evs if e[2] == "bench:phase"]
    ours = [e for e in evs if e[2].startswith(PROFILER_PREFIX)]
    assert len(phase) == 1 and ours
    # the program never emits the benchmark's own phase prefix
    assert [e[2] for e in evs if e[2].startswith("bench:")] == ["bench:phase"]
    assert all(_covers(phase[0], e) for e in ours)
    (outer,) = [e for e in ours if e[2] == "repro:outer"]
    (inner,) = [e for e in ours if e[2] == "repro:inner"]
    assert _covers(outer, inner)
    names = {e[2][len(PROFILER_PREFIX):] for e in ours}
    assert {"olap_serve", "route", "mirror_execute", "resolve", "dispatch",
            "finalize", "oltp_commit", "certify", "wal_emit",
            "rss_construct", "mirror_catch_up"} <= names
    serves = [e for e in ours if e[2] == "repro:olap_serve"]
    for e in ours:
        if e[2] in ("repro:resolve", "repro:dispatch", "repro:finalize"):
            assert any(_covers(s, e) for s in serves), e
    commits = [e for e in ours if e[2] == "repro:oltp_commit"]
    for e in ours:
        if e[2] in ("repro:certify", "repro:wal_emit"):
            assert any(_covers(c, e) for c in commits), e


def test_totals_carry_histogram_sum_and_count():
    reg = MetricRegistry()
    reg.counter("n_total").inc(2)
    a = reg.histogram("x_seconds", node="primary")
    b = reg.histogram("x_seconds", node="replica")
    a.observe(0.25)
    b.observe(0.5)
    b.observe(0.25)
    tot = reg.totals()
    assert tot["n_total"] == 2
    assert tot["x_seconds_count"] == 3
    assert tot["x_seconds_sum"] == pytest.approx(1.0)
    assert "x_seconds" not in tot
    reg.reset()
    assert reg.totals()["x_seconds_count"] == 0


def test_gc_counters_count_chains_visited_and_pruned_once_per_pass():
    from repro.mvcc.store import Store
    st = Store()
    st.chain("a").install(1, 1, 10)
    st.chain("a").install(2, 2, 20)
    st.chain("a").install(5, 3, 30)      # a: v0, 1, 2, 5
    st.chain("b").install(4, 4, 40)      # b: v0, 4
    st.chain("c")                        # c: v0 only, never a candidate
    v0 = REGISTRY.total("gc_chains_visited")
    p0 = REGISTRY.total("gc_chains_pruned")
    assert st.prune(3) == 2              # a drops v0 and v1; b keeps v0
    assert REGISTRY.total("gc_chains_visited") - v0 == 2
    assert REGISTRY.total("gc_chains_pruned") - p0 == 1
    assert st.prune(4) == 1              # b drops v0 now; a: 2, 5 stays
    assert REGISTRY.total("gc_chains_visited") - v0 == 4
    assert REGISTRY.total("gc_chains_pruned") - p0 == 2
    assert set(st.candidates) == {"a"}   # b is left with one version


def test_gc_never_visits_a_single_version_chain():
    from repro.mvcc.store import Store
    st = Store()
    st.chain("c")                        # c: v0 only
    st.chain("d").install(1, 1, 10)      # d: v0, 1
    v0 = REGISTRY.total("gc_chains_visited")
    assert st.prune(5) == 1              # d drops v0
    assert REGISTRY.total("gc_chains_visited") - v0 == 1
    assert not st.candidates
    assert st.prune(9) == 0              # nothing left to visit
    assert REGISTRY.total("gc_chains_visited") - v0 == 1


def test_gc_pruned_chain_rejoins_candidates_at_next_install():
    from repro.mvcc.store import Store
    st = Store()
    st.chain("a").install(1, 1, 10)
    assert st.prune(1) == 1 and not st.candidates     # a: 1
    st.chain("a").install(2, 2, 20)                   # a: 1, 2
    assert st.candidates == {"a": st.chain("a")}
    v0 = REGISTRY.total("gc_chains_visited")
    p0 = REGISTRY.total("gc_chains_pruned")
    assert st.prune(2) == 1
    assert REGISTRY.total("gc_chains_visited") - v0 == 1
    assert REGISTRY.total("gc_chains_pruned") - p0 == 1
    assert [v.commit_seq for v in st.chain("a").versions] == [2]


def test_gc_passes_are_spans_by_node():
    from repro.mvcc.htap import MultiNodeHTAP
    htap = MultiNodeHTAP("ssi+rss", n_replicas=2)
    t = htap.primary.begin()
    htap.primary.write(t, "k", 1)
    htap.primary.commit(t)
    htap.ship_log()
    before = {n: REGISTRY.hist_summary("gc_prune_seconds", node=n)["count"]
              for n in ("primary", "replica")}
    htap.gc_versions()
    after = {n: REGISTRY.hist_summary("gc_prune_seconds", node=n)["count"]
             for n in ("primary", "replica")}
    assert after["primary"] - before["primary"] == 1
    assert after["replica"] - before["replica"] == 2


def test_h2d_bytes_count_an_upload_and_not_a_store_cache_hit():
    from repro.core.wal import WalRecord
    from repro.tensorstore import PagedMirror
    mirror = PagedMirror()
    mirror.apply(WalRecord(lsn=1, type="commit", txn=1,
                           writes=(("a", 5), ("b", 9)), seq=1))
    keys = ("a", "b")
    pages = mirror.page_index(keys)
    up0 = REGISTRY.hist_summary("serve_upload_seconds")["count"]
    store = mirror._store_for(keys, pages)
    sent = store["data"].nbytes + store["ts"].nbytes
    assert mirror.h2d_bytes.value == sent > 0
    assert mirror._store_for(keys, pages) is store       # a cache hit
    assert mirror.h2d_bytes.value == sent
    assert REGISTRY.hist_summary("serve_upload_seconds")["count"] == up0 + 1
