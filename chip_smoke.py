#!/usr/bin/env python3
"""Drive the HTAP serve path once on one TPU chip, at one TPC-C warehouse.

    python chip_smoke.py

Three phases, each checked against the repo's own oracles:

  kernels      every serve-path Pallas kernel (scalar and flat grouped
               scan+aggregate, chunked two-stage grouped, delta fold),
               compiled, at the warehouse's page count, equal to its jnp
               reference in `repro.kernels.rss_scan_agg.ref`;
  single-node  `repro.mvcc.run_single_node` with RSS readers on the paged
               mirror, fused kernels, plan batching and materialized views;
               every plan result asserted equal to per-key engine reads;
  multi-node   `repro.mvcc.run_multi_node`, a primary and 2 replicas with
               session tokens, under the same checks.

The deployment is one TPC-C warehouse at the specification's per-warehouse
row counts (10 districts, 3,000 customers per district, 100,000 stock
rows): about 130k keys in 1 KiB pages.  The cuts from it are printed.

Earlier lines report, per phase, wall time, compile time, device memory
and the serve counters; they are rehearsal numbers, not a benchmark.  The
last line of stdout is one JSON object naming the device.  Without a TPU
the script exits non-zero and prints no result.

The compile cache goes where JAX_COMPILATION_CACHE_DIR says; when it is
unset, into `.jax_cache/` beside this script.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels.rss_scan_agg import ops as kops  # noqa: E402
from repro.kernels.rss_scan_agg.kernel import (  # noqa: E402
    rss_delta_fold, rss_scan_agg, rss_scan_agg_chunked, rss_scan_agg_grouped)
from repro.kernels.rss_scan_agg.ref import (  # noqa: E402
    rss_delta_fold_ref, rss_scan_agg_chunked_ref, rss_scan_agg_grouped_ref,
    rss_scan_agg_ref)
from repro.mvcc import run_multi_node, run_single_node  # noqa: E402
from repro.mvcc.workload import Scale  # noqa: E402

# TPC-C per-warehouse cardinalities (TPC-C standard specification, 5.11,
# clause 1.2.1 / 4.3.3.1): 10 districts, 3,000 customers per district,
# 100,000 stock rows per warehouse.
WAREHOUSE = Scale(warehouses=1, districts=10, customers=3000, items=100000)

# What this run leaves out of a TPC-C warehouse (ROADMAP R1, R3).
CUTS = (
    "warehouses: 1 (one chip's cell, no scale-out)",
    f"order_capacity: {WAREHOUSE.order_capacity} statically addressed orders"
    " per district vs TPC-C's 3,000 initial orders per district",
    "no order-line, new-order, history or item tables; no delivery or"
    " stock-level transactions",
    "rows carry one aggregable int field (int / {next_o_id, ytd} / "
    "{items, total}) instead of the spec's typed columns",
    "closed-loop round-based clients (4 OLTP, 4 OLAP), not open-loop"
    " arrivals",
)

_SLOTS, _ELEMS = 8, 32       # PagedMirror's default page: K slots, E elems
# driver rounds per HTAP phase: enough that every OLAP plan kind is served
# (and oracle-checked) many times at one warehouse
ROUNDS = 150


# --------------------------------------------------------------- clocks
class _CompileClock:
    """Backend compile time and count, read from JAX's monitoring events
    (a persistent-cache hit compiles nothing and is not counted)."""

    def __init__(self) -> None:
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)


def _phase(fn, *args, **kw) -> dict:
    clock = _CompileClock()
    t0 = time.perf_counter()
    try:
        out = fn(*args, **kw)
    finally:
        clock.close()
    out["wall_s"] = time.perf_counter() - t0
    out["compile_s"] = clock.seconds
    out["compiles"] = clock.count
    stats = jax.devices()[0].memory_stats() or {}
    out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    return out


def _timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, time.perf_counter() - t0


# --------------------------------------------------------------- phases
def store_pages(scale: Scale) -> int:
    """Pages the scale's reserved key families occupy, sublane-padded."""
    return -(-len(scale.key_families()) // 8) * 8


def kernel_phase(scale: Scale, *, seed: int = 0) -> dict:
    """Each serve-path kernel once at the scale's page count, with
    `interpret` resolved from the backend, equal to its jnp reference."""
    P = store_pages(scale)
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    bound = (2**31 - 1) // P           # whole-scan int32 bound (chunked)
    data = jnp.zeros((P, _SLOTS, _ELEMS), jnp.int32)
    data = data.at[:, :, 0].set(
        jax.random.randint(k[0], (P, _SLOTS), -1, 4))
    data = data.at[:, :, 1].set(
        jax.random.randint(k[1], (P, _SLOTS), -bound, bound))
    ts = jax.random.randint(k[2], (P, _SLOTS), 0, 4096)
    floor = 2048
    mem = jnp.sort(jax.random.choice(k[3], jnp.arange(floor + 1, 4096),
                                     (200,), replace=False)).astype(jnp.int32)

    def gprm(key, G):
        return jnp.stack([jax.random.choice(key, jnp.asarray([1, 3]), (G,)),
                          jax.random.choice(key, jnp.asarray([0, -2]), (G,)),
                          jax.random.randint(key, (G,), -bound, bound)],
                         axis=1).astype(jnp.int32)

    g_flat, g_chunk = 32, 64
    gid_flat = jax.random.randint(k[4], (P, 1), -1, g_flat)
    gid_chunk = jax.random.randint(k[5], (P, 1), -1, g_chunk)
    acc = jnp.zeros((64, 128), jnp.int32)
    acc = acc.at[:, 3].set(jnp.iinfo(jnp.int32).max)
    acc = acc.at[:, 4].set(jnp.iinfo(jnp.int32).min)
    delta = jax.random.randint(k[6], (256, 128), 0, 2)
    delta = delta.at[:, 0].set(jax.random.randint(k[6], (256,), -1, 64))
    delta = delta.at[:, 1].set(jax.random.randint(k[7], (256,), -999, 999))
    delta = delta.at[:, 3].set(jax.random.randint(k[5], (256,), -999, 999))

    cases = {
        "scalar": (rss_scan_agg, rss_scan_agg_ref,
                   (data, ts, mem, floor, 1, 0, 100), {}),
        "flat_grouped": (rss_scan_agg_grouped, rss_scan_agg_grouped_ref,
                         (data, ts, gid_flat, mem, floor),
                         {"n_groups": g_flat,
                          "group_params": gprm(k[4], g_flat)}),
        "chunked_grouped": (rss_scan_agg_chunked, rss_scan_agg_chunked_ref,
                            (data, ts, gid_chunk, mem, floor),
                            {"n_groups": g_chunk,
                             "group_params": gprm(k[5], g_chunk)}),
        "delta_fold": (rss_delta_fold, rss_delta_fold_ref, (acc, delta), {}),
    }
    out = {"pages": P, "kernels": {}}
    for name, (kern, ref, args, kw) in cases.items():
        got, first = _timed(kern, *args, **kw)
        _, steady = _timed(kern, *args, **kw)
        static = tuple(a for a in ("n_groups",) if a in kw)
        want = jax.jit(ref, static_argnames=static)(*args, **kw)
        assert np.array_equal(np.asarray(got), np.asarray(want)), name
        out["kernels"][name] = {"first_call_s": first, "second_call_s": steady,
                                "shape": tuple(got.shape)}
    return out


def _serve_checks(m, *, min_steps: int) -> dict:
    """The serve-path contract every HTAP phase must meet: RSS readers
    never abort or wait, the kernels ran, views served, and every OLAP
    plan kind was served (and oracle-checked) at least `min_steps`
    times."""
    steps = {"scan": m.olap_scan_steps, "agg": m.olap_agg_steps,
             "multi_agg": m.olap_multi_agg_steps,
             "group": m.olap_group_steps}
    stats = dict(kops.LAUNCH_STATS)
    assert m.olap_aborts == 0, m.olap_aborts
    assert m.olap_wait_rounds == 0, m.olap_wait_rounds
    assert stats["pallas_calls"] > 0, stats
    assert m.olap_view_hits > 0, m.olap_view_hits
    assert min(steps.values()) >= min_steps, steps
    assert m.session_token_violations == 0, m.session_token_violations
    return {"oltp_commits": m.oltp_commits, "oltp_aborts": m.oltp_aborts,
            "olap_commits": m.olap_commits, "olap_aborts": m.olap_aborts,
            "olap_wait_rounds": m.olap_wait_rounds,
            "plan_steps_checked": steps,
            "pallas_calls": stats["pallas_calls"],
            "dispatches": stats["dispatches"],
            "modes": {x: stats[x] for x in ("host", "flat", "chunked")},
            "overflow_fallbacks": stats["overflow_fallbacks"],
            "delta_folds": stats["delta_folds"],
            "view_hits": m.olap_view_hits,
            "view_fallbacks": m.olap_view_fallbacks}


_HTAP = dict(olap_mode="ssi+rss", oltp_clients=4, olap_clients=4,
             olap_scan=True, paged_olap=True, check_scans=True,
             batch_plans=True, materialize=True)


def single_node_phase(scale: Scale, *, rounds: int, seed: int = 0,
                      min_steps: int = 2) -> dict:
    m = run_single_node(rounds=rounds, seed=seed, scale=scale, **_HTAP)
    return _serve_checks(m, min_steps=min_steps)


def multi_node_phase(scale: Scale, *, rounds: int, seed: int = 0,
                     min_steps: int = 2) -> dict:
    m = run_multi_node(rounds=rounds, seed=seed, scale=scale, n_replicas=2,
                       session_tokens=True, **_HTAP)
    out = _serve_checks(m, min_steps=min_steps)
    out["served_by_replica"] = m.olap_served_by
    out["token_violations"] = m.session_token_violations
    return out


# ----------------------------------------------------------------- main
def _configure_compile_cache() -> str:
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    path = str(REPO / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the kernel data and the driver's clients")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device}")
    print(f"compile cache: {_configure_compile_cache()}")
    scale = WAREHOUSE
    keys = len(scale.key_families())
    page_bytes = _SLOTS * _ELEMS * 4
    print(f"deployment: {scale}; reserved keys {keys}; page store "
          f"{store_pages(scale) * page_bytes} bytes ({page_bytes} B/page)")
    for cut in CUTS:
        print(f"cut: {cut}")
    phases = (("kernels", kernel_phase, dict(seed=args.seed)),
              ("single_node", single_node_phase,
               dict(rounds=ROUNDS, seed=args.seed)),
              ("multi_node", multi_node_phase,
               dict(rounds=ROUNDS, seed=args.seed)))
    for name, fn, kw in phases:
        res = _phase(fn, scale, **kw)
        print(f"phase {name}: " + json.dumps(res, default=str))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
