"""One benchmark run: build the deployment, load it, warm it up, drive the
traffic in lockstep rounds for a measured window, and check what was
served against the plain reference.

The round loop and the clients are copies of `repro.mvcc.driver`
(`run_single_node` / `run_multi_node`, `_OltpClient`, `_OlapClientSingle` /
`_OlapClientMulti`): every round each client advances one step, and each
analytic step is served by one `olap_execute` call.  The harness touches only the
program's public surface: the facades `SingleNodeHTAP` / `MultiNodeHTAP`,
`Engine` begin/read/write/commit, the plan IR, `refresh_rss`, `ship_log`,
`gc_versions`, `repro.obs.reset_run` and `REGISTRY`.  The facade's options
come from the configuration's own keys, and its optional `facade` object
passes further ones (`facade_options`).

It times the calls into each layer from here (`Spans`), and in a traced
run wraps each phase of a round in a `jax.profiler.TraceAnnotation`, so the
trace can attribute the device's idle gaps to what the host was doing.
"""

from __future__ import annotations

import bisect
import contextlib
import importlib.util
import json
import random
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path

from . import cost
from .reference import Reference, consistency, same
from .traffic import Mix, Schema

BENCH = Path(__file__).resolve().parent
TRACE_PREFIX = "bench:"


# the facades' options that `Run` sets from a configuration's own keys
HARNESS_OPTIONS = frozenset({
    "olap_mode", "reserve_keys", "materialize", "certifier", "resolve_cache",
    "paged", "paged_olap", "n_replicas", "route_policy", "max_staleness"})


# ------------------------------------------------------------------ files
def load_config(name: str, root: Path = BENCH) -> dict:
    """The configuration `<root>/configs/<name>.json`."""
    cfg = json.loads((root / "configs" / f"{name}.json").read_text())
    if cfg["name"] != name:
        raise ValueError(f"config file {name}.json names {cfg['name']!r}")
    facade_options(cfg)
    return cfg


def facade_options(cfg: dict) -> dict:
    """The configuration's `facade` object: further keyword arguments of
    the facade's constructor.  An option that the harness sets itself from
    the configuration's own keys is refused, so each has one place."""
    opts = dict(cfg.get("facade", {}))
    taken = sorted(HARNESS_OPTIONS & set(opts))
    if taken:
        raise ValueError(f"configuration {cfg['name']!r}: facade options "
                         f"{taken} are set by the harness from the "
                         f"configuration's own keys")
    return opts


def load_reader(metric: str, root: Path = BENCH):
    """The per-layer metric's reader, `<root>/layers/<metric>.py:read`."""
    path = root / "layers" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_layer_{metric}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def percentile(values, q: float) -> float:
    """The q-th percentile with linear interpolation between the two
    nearest ranks (numpy's default method)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


# ------------------------------------------------------------------ spans
class Spans:
    """Host time per phase, from this process's clock.  With `annotate`,
    each phase is also a `jax.profiler.TraceAnnotation` named
    `bench:<phase>`."""

    def __init__(self, annotate: bool = False) -> None:
        self.total: dict = {}                 # phase -> [seconds, calls]
        self.annotate = annotate
        self.recording = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(TRACE_PREFIX + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            if ann is not None:
                ann.__exit__(None, None, None)
            if self.recording:
                ent = self.total.setdefault(name, [0.0, 0])
                ent[0] += dt
                ent[1] += 1


class CompileClock:
    """Programs built inside a stretch of the run, from JAX's monitoring
    events (the clock of `chip_smoke.py`, extended): backend compiles and
    persistent-cache loads, and the seconds spent tracing, lowering,
    compiling and loading them."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _BUILD = (_COMPILE, "/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")
    _CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        import jax
        self._jax = jax
        self._lock = threading.Lock()   # the warm-up compiles on threads
        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event: str, secs: float, **kw) -> None:
        with self._lock:
            if event == self._COMPILE:
                self.compiles += 1
                self.names.append(str(kw.get("fun_name")))
            if event in self._BUILD:
                self.build_s += secs

    def _on_event(self, event: str, **_) -> None:
        if event == self._CACHE_HIT:
            with self._lock:
                self.cache_loads += 1

    def reset(self) -> None:
        self.compiles, self.cache_loads, self.build_s = 0, 0, 0.0
        self.names: list = []        # of the programs compiled

    def __str__(self) -> str:
        return (f"{self.compiles} compiles and {self.cache_loads} cache "
                f"loads, {self.build_s:.3f} s tracing, lowering, compiling "
                f"and loading")

    def close(self) -> None:
        self._jax.monitoring.unregister_event_duration_listener(self._on_dur)
        self._jax.monitoring.unregister_event_listener(self._on_event)


# -------------------------------------------------------------- lowering
def to_plan(spec: tuple):
    """A query spec as the program's plan IR."""
    from repro.tensorstore.version_store import (AggOp, AggPlan,
                                                 GroupByPlan, MultiAggPlan,
                                                 ScanPlan)
    kind = spec[0]
    if kind == "scan":
        return ScanPlan(spec[1])
    if kind == "agg":
        return AggPlan(spec[1], AggOp(*spec[2]))
    if kind == "multi":
        return MultiAggPlan(spec[1], tuple(AggOp(*op) for op in spec[2]))
    if kind == "group":
        return GroupByPlan(tuple(spec[1]),
                           tuple(AggOp(*op) for op in spec[2]))
    raise ValueError(f"unknown spec {kind!r}")


def _shape_of(spec: tuple) -> tuple:
    """What of an aggregate spec decides its kernels' shapes: its kind, its
    key counts, and its ops without their threshold values."""
    keys = (tuple(len(g) for g in spec[1]) if spec[0] == "group"
            else len(spec[1]))
    ops = spec[2:3] if spec[0] == "agg" else spec[2]
    return (spec[0], keys, tuple((op[0], op[1], op[2] is None) for op in ops))


def _snap_of(snapshot) -> tuple:
    """(floor_seq, member seqs) of the program's RSS snapshot."""
    if snapshot.member_seqs is None:
        raise ValueError("served snapshot carries no member seqs")
    return int(snapshot.floor_seq), tuple(int(s) for s in
                                          snapshot.member_seqs)


# ------------------------------------------------------------ the run
@dataclass
class Window:
    """What the measured window saw."""
    t0: float = 0.0
    t1: float = 0.0
    commits: int = 0                 # terminal commits acknowledged
    oltp_attempts: int = 0           # transactions dealt (not their retries)
    oltp_aborts: int = 0             # certification aborts, each retried
    queries_begun: int = 0
    query_s: list = field(default_factory=list)   # completed queries
    olap_aborts: int = 0
    olap_waits: int = 0
    session_regressions: int = 0     # a stream's snapshot LSN went back
    plan_serves: int = 0
    kernel_bytes: int = 0            # scan bytes of serves that hit kernels
    served: list = field(default_factory=list)    # (spec, snap, result, t)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Terminal:
    """A TPC-C terminal: one transaction at a time, dealt from its deck; a
    transaction aborted by certification is retried (the same card, with
    the generator state it was first drawn with)."""

    def __init__(self, run: "Run", rng: random.Random) -> None:
        self.run, self.rng = run, rng
        self.deck = run.mix.terminal_deck(rng)
        self.txn = self.gen = self.pending = None
        self.retry = None                    # (card, rng state) to redo

    def _begin(self) -> None:
        if self.retry is None:
            self.retry = (self.deck.deal(), self.rng.getstate())
            self.run.window.oltp_attempts += self.run.recording
        else:
            self.rng.setstate(self.retry[1])
        self.gen, read_only = self.run.mix.transaction(self.retry[0],
                                                       self.rng)
        self.txn = self.run.engine.begin(read_only=read_only)
        self.pending = None

    def _aborted(self) -> None:
        self.run.window.oltp_aborts += self.run.recording
        self.txn = None

    def step(self) -> None:
        from repro.mvcc.engine import SerializationFailure, Status
        if self.txn is None:
            self._begin()
            return
        if self.txn.status == Status.ABORTED:
            self._aborted()
            return
        eng = self.run.engine
        try:
            step = self.gen.send(self.pending)
            self.pending = None
        except StopIteration:
            writes = list(self.txn.writes.items())
            try:
                eng.commit(self.txn)
            except SerializationFailure:
                self._aborted()
                return
            self.run.acknowledge(self.txn.end_seq, writes)
            self.txn = None
            self.retry = None
            return
        try:
            if step[0] == "r":
                self.pending = eng.read(self.txn, step[1])
            elif step[0] == "w":
                eng.write(self.txn, step[1], step[2])
        except SerializationFailure:
            self._aborted()


class Stream:
    """An analytic stream: one query at a time on its own snapshot —
    an RSS-protected transaction on the unified node, a routed snapshot
    handle (with a session token) on the decoupled cluster."""

    def __init__(self, run: "Run", rng: random.Random, session=None) -> None:
        self.run, self.rng, self.session = run, rng, session
        self.deck = run.mix.stream_deck(rng)
        self.ctx = self.gen = self.pending = None
        self.t_begin = 0.0
        self.last_lsn = -1

    def _begin(self) -> None:
        run = self.run
        if run.unified:
            self.ctx = run.htap.olap_begin()
            if self.ctx is None:
                run.window.olap_waits += run.recording
                return
        else:
            self.ctx = run.htap.olap_snapshot(session=self.session)
            lsn = self.ctx[3].lsn
            if lsn < self.last_lsn:
                run.window.session_regressions += run.recording
            self.last_lsn = max(self.last_lsn, lsn)
        self.gen = run.mix.query(self.deck.deal(), self.rng)
        self.pending = None
        self.t_begin = time.perf_counter()
        run.window.queries_begun += run.recording

    def _finish(self, ok: bool) -> None:
        run = self.run
        if run.unified:
            if ok:
                run.htap.olap_commit(self.ctx)
            else:
                run.htap.olap_abandon(self.ctx)
        else:
            run.htap.olap_release(self.ctx)
        if ok and run.recording:
            run.window.query_s.append(time.perf_counter() - self.t_begin)
        self.ctx = None

    def step(self) -> None:
        from repro.mvcc.engine import SerializationFailure, Status
        run = self.run
        if self.ctx is None:
            self._begin()
            return
        if run.unified and self.ctx.status == Status.ABORTED:
            run.window.olap_aborts += run.recording
            self._finish(False)
            return
        try:
            step = self.gen.send(self.pending)
            self.pending = None
        except StopIteration:
            try:
                self._finish(True)
            except SerializationFailure:
                run.window.olap_aborts += run.recording
                self.ctx = None
            return
        if step[0] != "olap":
            return
        spec = step[1]
        try:
            self.pending = run.serve(self.ctx, spec)
        except SerializationFailure:
            run.window.olap_aborts += run.recording
            self._finish(False)


class Run:
    """One deployment under one mix."""

    def __init__(self, cfg: dict, mix_name: str, seed: int, *,
                 annotate: bool = False) -> None:
        from repro.mvcc.htap import MultiNodeHTAP, SingleNodeHTAP

        self.cfg = cfg
        self.schema = Schema.from_config(cfg)
        self.mix = Mix.load(mix_name, self.schema)
        self.seed = seed
        self.spans = Spans(annotate)
        self.ref = Reference()
        self.window = Window()
        self.recording = False
        self.unified = cfg["architecture"] == "unified"
        views = [to_plan(self.schema.dashboards[n]) for n in cfg["views"]]
        common = dict(reserve_keys=self.schema.key_families(),
                      materialize=views or None,
                      certifier=cfg["certifier"],
                      resolve_cache=cfg["resolve_cache"],
                      **facade_options(cfg))
        if self.unified:
            self.htap = SingleNodeHTAP(cfg["olap_mode"], paged=True, **common)
            self.engine = self.htap.engine
        else:
            self.htap = MultiNodeHTAP(
                cfg["olap_mode"], paged_olap=True,
                n_replicas=cfg["replicas"],
                route_policy=cfg["route_policy"],
                max_staleness=cfg["max_staleness"], **common)
            self.engine = self.htap.primary
        self.round = 0
        self.clients: list = []
        self.warm_specs: dict = {}      # plan shape -> a spec served in set-up

    # ------------------------------------------------------------ set-up
    def load(self) -> None:
        """The initial load (one transaction) and the first refresh or
        ship, then the clients, seeded from the run's seed."""
        rng = random.Random(self.seed)
        t = self.engine.begin()
        rows = self.schema.initial_rows(random.Random(rng.random()))
        for key, value in rows:
            self.engine.write(t, key, value)
        self.engine.commit(t)
        self.ref.commit(t.end_seq, rows, time.perf_counter())
        if self.unified:
            self.htap.refresh_rss()
        else:
            self.htap.ship_log()
        cl = self.cfg["clients"]
        self.clients = [Terminal(self, random.Random(rng.random()))
                        for _ in range(cl["terminals_per_warehouse"]
                                       * self.schema.warehouses)]
        self.clients += [
            Stream(self, random.Random(rng.random()),
                   session=(self.htap.session()
                            if self.cfg.get("session_tokens") else None))
            for _ in range(cl["analytic_streams"])]

    # ------------------------------------------------------------ rounds
    def acknowledge(self, seq: int, writes) -> None:
        now = time.perf_counter()
        self.ref.commit(seq, writes, now)
        self.window.commits += self.recording

    def serve(self, ctx, spec: tuple):
        """Serve one plan through the facade's `olap_execute`; in the window,
        record it with its snapshot and the time its result came back."""
        from repro.obs import REGISTRY
        call = lambda: self.htap.olap_execute(ctx, to_plan(spec))  # noqa: E731
        if not self.recording:
            if spec[0] != "scan":
                self.warm_specs.setdefault(_shape_of(spec), spec)
            return call()
        before = REGISTRY.total("kernel_launch_pallas_calls") - \
            REGISTRY.total("kernel_launch_delta_folds")
        result = call()
        t = time.perf_counter()
        scanned = REGISTRY.total("kernel_launch_pallas_calls") - \
            REGISTRY.total("kernel_launch_delta_folds") > before
        w = self.window
        snap = _snap_of(ctx.rss if self.unified else ctx[3])
        w.served.append((spec, snap, result, t))
        w.plan_serves += 1
        if scanned and spec[0] != "scan":
            w.kernel_bytes += cost.scan_bytes(
                spec, len(snap[1]), slots=self.cfg["page"]["slots"])
        return result

    def step_round(self) -> None:
        cfg, rnd, sp = self.cfg, self.round, self.spans
        if self.unified:
            if rnd % cfg["refresh_every"] == 0:
                with sp("refresh_rss"):
                    self.htap.refresh_rss()
        else:
            for i in range(cfg["replicas"]):
                if rnd % (cfg["ship_every"] * (1 + i * cfg["ship_skew"])) \
                        == 0:
                    with sp("ship_log"):
                        self.htap.ship_log(replica=i)
            if rnd % cfg["gc_every"] == 0:
                with sp("gc_versions"):
                    self.htap.gc_versions()
        n_term = len(self.clients) - self.cfg["clients"]["analytic_streams"]
        with sp("oltp"):
            for cl in self.clients[:n_term]:
                cl.step()
        with sp("olap"):
            for cl in self.clients[n_term:]:
                cl.step()
        self.round += 1

    def warm_up(self, rounds: int) -> None:
        for _ in range(rounds):
            self.step_round()

    def settle_rounds(self) -> int:
        """Twice the longest refresh, ship or GC cadence, in rounds.  Run
        after a pause in set-up (the kernel warm-up), they let the RSS take
        in every commit acknowledged before it, so that no snapshot served
        in the window is stale by the length of that pause."""
        cfg = self.cfg
        if self.unified:
            cadence = cfg["refresh_every"]
        else:
            cadence = max(cfg["gc_every"], cfg["ship_every"]
                          * (1 + (cfg["replicas"] - 1) * cfg["ship_skew"]))
        return 2 * cadence

    def warm_plans(self) -> list:
        """The program's plans of every aggregate shape served in the
        warm-up and of every registered view."""
        specs = list(self.warm_specs.values()) + \
            [self.schema.dashboards[n] for n in self.cfg["views"]]
        return [to_plan(s) for s in specs]

    def measure(self, seconds: float) -> Window:
        """Reset the program's registry and run rounds for `seconds`."""
        from repro.obs import reset_run
        reset_run()
        self.recording = self.spans.recording = True
        w = self.window
        w.t0 = time.perf_counter()
        deadline = w.t0 + seconds
        with self.spans("window"):
            while time.perf_counter() < deadline:
                self.step_round()
        w.t1 = time.perf_counter()
        self.recording = self.spans.recording = False
        return w

    def release_program(self) -> None:
        """Drop the program's state (its device buffers with it) once the
        window's numbers are read; the check needs only the record."""
        from repro.obs import REGISTRY
        self.registry_totals = REGISTRY.totals()
        self.stage_sums = REGISTRY.hist_group("olap_stage_seconds", "stage")
        self.htap = self.engine = None
        self.clients = []

    # ------------------------------------------------------------ results
    def end_to_end(self, setup_s: float) -> dict:
        w = self.window
        stale = [self.ref.staleness(snap[0], snap[1], t)
                 for _spec, snap, _r, t in w.served]
        return {
            "oltp_commits_per_s": w.commits / w.seconds,
            "olap_queries_per_s": len(w.query_s) / w.seconds,
            "olap_query_p95_ms": percentile(w.query_s, 95) * 1e3,
            "snapshot_staleness_p95_ms": percentile(stale, 95) * 1e3,
            "setup_s": setup_s,
        }

    def plant_lost_updates(self) -> None:
        """The serializability control, planted in the program: the engine
        commits each writer as if it had begun just now, so
        first-committer-wins never sees a version committed after the
        writer's snapshot, and certification is off.  Two concurrent
        read-modify-writes of one key then both commit, the later one over
        the earlier one's update."""
        eng = self.engine
        real = eng.commit

        def commit(t) -> None:
            t.begin_seq = eng.seq
            real(t)
        eng.commit = commit
        eng.certifier.on_rw_edge = lambda reader, writer: None
        eng.certifier.on_precommit = lambda t: None

    def check(self, *, sample: int, control=()) -> dict:
        """Compare a sample of the window's served results, drawn from the
        seed, with the reference at each result's snapshot, and the
        acknowledged writes with the TPC-C consistency conditions.  With
        `"latest"` among `control` the reference itself stands in the
        program's place with its snapshot guarantee broken: it answers from
        every commit acknowledged before the serve."""
        unknown = set(control) - {"latest", "lost_updates"}
        if unknown:
            raise ValueError(f"unknown control {sorted(unknown)}")
        w = self.window
        rng = random.Random(self.seed ^ 0x5EED)
        idx = sorted(rng.sample(range(len(w.served)),
                                min(sample, len(w.served))))
        bad = 0
        for i in idx:
            spec, (floor, mem), result, t = w.served[i]
            if "latest" in control:
                k = bisect.bisect_right(self.ref.ack_t, t)
                result = self.ref.evaluate(
                    spec, self.ref.ack_seq[k - 1] if k else 0)
            if not same(result, self.ref.evaluate(spec, floor, mem)):
                bad += 1
        checks = {"mismatched_results": [bad, 0]}
        checks.update({k: [v, 0] for k, v in consistency(self.ref).items()})
        checks["olap_aborts"] = [w.olap_aborts, 0]
        checks["olap_waits"] = [w.olap_waits, 0]
        if not self.unified:
            checks["token_violations"] = [
                self.registry_totals.get("cluster_token_violations", 0), 0]
            checks["session_regressions"] = [w.session_regressions, 0]
        checks["results_checked"] = [len(idx), 1]
        return checks


@dataclass
class LayerInput:
    """What a per-layer metric's reader (`bench/layers/<metric>.py`) gets:
    the window's record, the harness's host spans, the program's registry
    totals and serve-stage histograms at the window's end, the trace
    summary (traced runs) and the device's peaks."""
    window: Window
    spans: dict                      # phase -> [seconds, calls]
    totals: dict                     # registry counter families
    stages: dict                     # olap_stage_seconds by stage
    trace: object                    # trace_reduce.TraceSummary or None
    peaks: dict

    @classmethod
    def of(cls, run: Run, trace, peaks: dict) -> "LayerInput":
        return cls(run.window, run.spans.total, run.registry_totals,
                   run.stage_sums, trace, peaks)


def checks_pass(checks: dict) -> bool:
    """Every compared number within its limit: at most the limit, except
    `results_checked`, which has to reach its limit."""
    return all((v >= lim) if name == "results_checked" else (v <= lim)
               for name, (v, lim) in checks.items())
