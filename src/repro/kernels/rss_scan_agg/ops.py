"""Public ops: fused scan+aggregate (scalar, grouped flat-lane, grouped
chunked two-stage), kernel or jnp — plus the shape dispatcher that picks
the grouped strategy and the host-side int32 overflow guard.

Dispatch (`select_grouped_mode`, flash-linear-attention's chunk /
fused_recurrent idiom): small scans go "host" (launch overhead dominates
— the mirror decodes and aggregates in Python), few groups go "flat"
(all-G accumulator lanes per grid step), many groups go "chunked"
(two-stage tiled-group reduction).  Thresholds come from a CPU
interpret-mode sweep and have not been measured on the chip; they are
overridable — per call, or globally via the REPRO_GROUPED_MODE env var.

Overflow guard: device partials are int32.  The flat path only needs one
BP-page block's partial to fit (|field| max * BP < 2**31).  BP is never
below 8 (the TPU's sublane tile), so when the store's field magnitude
violates the bound at BP=8 the op computes per-page partials with the
jnp reference instead (a single int32 value cannot overflow) and folds
them exactly on host.  The chunked path folds ON DEVICE, so it needs the
whole-scan bound (|field| max * P < 2**31) and falls back to flat-lane
when violated.  Both fallbacks count `overflow_fallbacks`.  The |field|
max comes from the caller (`maxabs=`: the paged mirror keeps it per page
as it writes); `field_maxabs` reads the same number back from a device
store.
`LAUNCH_STATS` counts dispatches, pallas calls, chosen modes and
fallbacks — the driver and verify.sh read it to assert
one-launch-per-fused-batch.

`interpret` passes through to the kernels, which resolve None from the
backend (`repro.kernels.config`)."""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...obs import REGISTRY, StatsView, span
from .kernel import (rss_delta_fold, rss_scan_agg, rss_scan_agg_chunked,
                     rss_scan_agg_grouped, tree_fold_partials)
from .ref import (rss_delta_fold_ref, rss_scan_agg_chunked_ref,
                  rss_scan_agg_grouped_ref, rss_scan_agg_ref)

# jitted ref entry points: the use_kernel=False paths serve fused
# dispatches too (benches, oracle runs), where eager per-op dispatch of
# the segment/scatter refs would swamp the fusion win
_scan_agg_ref = jax.jit(rss_scan_agg_ref, static_argnames=("block_pages",))
_grouped_ref = jax.jit(rss_scan_agg_grouped_ref,
                       static_argnames=("n_groups", "block_pages"))
_chunked_ref = jax.jit(rss_scan_agg_chunked_ref,
                       static_argnames=("n_groups", "rows_per_step",
                                        "fold_chunks"))

_I32_MAX = jnp.iinfo(jnp.int32).max
_I32_MIN = jnp.iinfo(jnp.int32).min

BLOCK_PAGES = 8                   # flat/scalar grid block: one sublane tile

# --- shape dispatch ---------------------------------------------------------

GROUPED_MODE_ENV = "REPRO_GROUPED_MODE"
GROUPED_MODES = ("host", "flat", "chunked")
# thresholds from a CPU interpret-mode sweep, not yet measured on the
# chip: below HOST_MODE_MAX_PAGES the launch overhead beat any fusion win
# for a single plan; flat-lane won while all-G lanes still fit useful
# VMEM — the interpreter's flat/chunked crossover sat between G=32 and
# G=64 at P=1024..4096 (ROADMAP.md, D4).
HOST_MODE_MAX_PAGES = 64
FLAT_MODE_MAX_GROUPS = 32

# process-wide launch accounting — a registry view (series
# kernel_launch_*), so snapshots/export/reset compose with every other
# layer's metrics; dict-shaped API preserved for existing readers
LAUNCH_STATS = StatsView(REGISTRY, "kernel_launch",
                         ("dispatches", "pallas_calls", "host", "flat",
                          "chunked", "overflow_fallbacks", "delta_folds"))


def reset_launch_stats() -> dict:
    """Atomically zero LAUNCH_STATS and return the pre-reset snapshot."""
    return LAUNCH_STATS.reset()


def select_grouped_mode(n_pages: int, n_groups: int, n_plans: int = 1, *,
                        override: Optional[str] = None) -> str:
    """Pick the grouped execution strategy for a (P, G, n_plans) shape:
    "host" (decode + Python aggregate), "flat" (all-G accumulator lanes),
    or "chunked" (two-stage tiled-group reduction).  `override` (or the
    REPRO_GROUPED_MODE env var) forces a mode; "auto" defers to the
    shape heuristic.  Fused batches (n_plans > 1) never pick "host" —
    one device launch is the point of batching."""
    mode = override or os.environ.get(GROUPED_MODE_ENV) or "auto"
    if mode != "auto":
        assert mode in GROUPED_MODES, mode
        return mode
    if n_pages < HOST_MODE_MAX_PAGES and n_plans == 1:
        return "host"
    if n_groups <= FLAT_MODE_MAX_GROUPS:
        return "flat"
    return "chunked"


# --- overflow guard ---------------------------------------------------------

# the overflow guard's bound lookup, once per call
_MAXABS_H = REGISTRY.histogram("serve_maxabs_seconds")


def field_maxabs(store: dict) -> int:
    """Largest |aggregable field| (payload element 1) across every slot of
    the store, by a device-to-host copy of the whole store: the reference
    that a caller's `maxabs` must equal."""
    col = np.asarray(store["data"])[:, :, 1]
    return int(np.abs(col.astype(np.int64)).max()) if col.size else 0


def guard_maxabs(maxabs: int) -> int:
    """The host-side input to the int32 partial bounds: the caller's
    `maxabs`, which must equal `field_maxabs` of the scanned store."""
    with span("serve_maxabs", _MAXABS_H):
        return int(maxabs)


def safe_block_pages(maxabs: int) -> Optional[int]:
    """BLOCK_PAGES when an 8-page block partial provably fits int32
    (maxabs * 8 < 2**31), else None: no thinner block may reach the TPU
    compiler, so the caller takes its exact per-page fallback."""
    return BLOCK_PAGES if maxabs <= (2**31 - 1) // BLOCK_PAGES else None


def scan_bound_ok(maxabs: int, n_pages: int) -> bool:
    """True when a whole-scan int32 sum provably cannot wrap — the bound
    the chunked path's DEVICE fold needs (host folds are exact Python
    ints and only need the per-block bound)."""
    return n_pages == 0 or maxabs <= (2**31 - 1) // max(1, n_pages)


# --- scalar path ------------------------------------------------------------

def fold_partials(partials) -> list[int]:
    """Fold [n_blocks, 7] per-block device partials into the final [sum,
    count, count_below, min, max, count_above, sum_below] — exact past
    int32: partials are int32,
    so an int64 host accumulation cannot wrap below 2**32 blocks (a store
    that large doesn't fit an int32 page index anyway)."""
    rows = np.asarray(partials, dtype=np.int64)
    if not rows.shape[0]:
        return [0, 0, 0, int(_I32_MAX), int(_I32_MIN), 0, 0]
    return [int(rows[:, 0].sum()), int(rows[:, 1].sum()),
            int(rows[:, 2].sum()), int(rows[:, 3].min()),
            int(rows[:, 4].max()), int(rows[:, 5].sum()),
            int(rows[:, 6].sum())]


def snapshot_agg_members(store: dict, member_ts, floor=0, *,
                         tag_main: int, tag_alt: int = -2,
                         threshold: Optional[int] = None,
                         maxabs: int,
                         use_kernel: bool = True,
                         interpret: Optional[bool] = None) -> list[int]:
    """Fused RSS membership scan + aggregate over a paged store
    {'data': [P,K,E] int32, 'ts': [P,K]}: resolve visibility (ts <= floor
    or ts in the sorted member_ts array — `rss_gather` semantics; an empty
    member array with floor = watermark gives SI-V prefix visibility) and
    reduce payload element 1 over visible pages tagged tag_main/tag_alt,
    all in ONE device pass.

    Returns the folded [sum, count, count_below, min, max, count_above,
    sum_below] as Python ints
    (per-block int32 partials on device, exact fold on host);
    `tensorstore.version_store.finalize_agg` picks the requested statistic
    (min/max carry sentinels when count == 0).  When the store's field
    magnitude could wrap an 8-page block partial, the jnp reference
    computes per-page partials instead (counted in
    `overflow_fallbacks`); `maxabs` is that magnitude, given by the caller
    (`guard_maxabs`)."""
    thresh = _I32_MAX if threshold is None else int(threshold)
    bp = safe_block_pages(guard_maxabs(maxabs))
    if bp is None:
        LAUNCH_STATS["overflow_fallbacks"] += 1
    if bp is None or not use_kernel:
        partials = _scan_agg_ref(store["data"], store["ts"], member_ts,
                                 floor, tag_main, tag_alt, thresh,
                                 block_pages=bp or 1)
    else:
        LAUNCH_STATS["pallas_calls"] += 1
        partials = rss_scan_agg(store["data"], store["ts"], member_ts,
                                floor, tag_main, tag_alt, thresh,
                                block_pages=bp, interpret=interpret)
    return fold_partials(partials)


# --- grouped paths ----------------------------------------------------------

def fold_group_partials(partials) -> list[list[int]]:
    """Fold [n_blocks, G, 7] per-block per-group device partials into G
    final [sum, count, count_below, min, max, count_above, sum_below]
    rows — vectorized int64
    accumulation, same overflow discipline as `fold_partials`."""
    rows = np.asarray(partials, dtype=np.int64)
    n_groups = rows.shape[1]
    if not rows.shape[0]:
        return [[0, 0, 0, int(_I32_MAX), int(_I32_MIN), 0, 0]
                for _ in range(n_groups)]
    folded = np.concatenate([rows[:, :, :3].sum(axis=0),
                             rows[:, :, 3].min(axis=0)[:, None],
                             rows[:, :, 4].max(axis=0)[:, None],
                             rows[:, :, 5:7].sum(axis=0)], axis=1)
    return folded.tolist()


def snapshot_group_agg_members(store: dict, gid, n_groups: int,
                               member_ts, floor=0, *,
                               tag_main: int = 1, tag_alt: int = -2,
                               threshold: Optional[int] = None,
                               group_params=None,
                               maxabs: int,
                               use_kernel: bool = True,
                               interpret: Optional[bool] = None) \
        -> list[list[int]]:
    """GROUP BY variant of `snapshot_agg_members` (flat-lane strategy):
    `gid` maps each page of the store to an accumulator lane
    (0..n_groups-1; -1 = no group), and ONE fused device pass resolves
    visibility AND reduces every group — a small [n_groups, 5] tile back
    instead of one scalar per group.  group_params [n_groups, 3] int32
    rows of (tag_main, tag_alt, threshold) give each lane its own config
    (fused multi-plan batches); None broadcasts the scalar args.

    Returns n_groups folded [sum, count, count_below, min, max,
    count_above, sum_below] rows as
    Python ints; a group no visible page maps to is [0, 0, 0, INT32_MAX,
    INT32_MIN, 0, 0] (count disambiguates — `finalize_agg` folds the
    sentinels
    to 0).  Same overflow fallback as `snapshot_agg_members`."""
    thresh = _I32_MAX if threshold is None else int(threshold)
    gid = jnp.asarray(np.asarray(gid, np.int32).reshape(-1, 1))
    bp = safe_block_pages(guard_maxabs(maxabs))
    if bp is None:
        LAUNCH_STATS["overflow_fallbacks"] += 1
    if group_params is not None:
        group_params = jnp.asarray(np.asarray(group_params, np.int32))
    if bp is None or not use_kernel:
        partials = _grouped_ref(
            store["data"], store["ts"], gid, member_ts, floor,
            tag_main, tag_alt, thresh, n_groups=n_groups,
            group_params=group_params, block_pages=bp or 1)
    else:
        LAUNCH_STATS["pallas_calls"] += 1
        partials = rss_scan_agg_grouped(
            store["data"], store["ts"], gid, member_ts, floor,
            tag_main, tag_alt, thresh, n_groups=n_groups,
            block_pages=bp, group_params=group_params,
            interpret=interpret)
    return fold_group_partials(partials)


def snapshot_group_agg_chunked(store: dict, gid, n_groups: int,
                               member_ts, floor=0, *,
                               tag_main: int = 1, tag_alt: int = -2,
                               threshold: Optional[int] = None,
                               group_params=None,
                               group_tile: int = 8,
                               use_kernel: bool = True,
                               interpret: Optional[bool] = None) \
        -> list[list[int]]:
    """Chunked two-stage GROUP BY: select pass + tiled-group reduce +
    device tree fold (two pallas calls, [G, 7] back).  Same semantics as
    `snapshot_group_agg_members`; requires the whole-scan int32 bound —
    callers should go through `grouped_agg_auto`, which checks it and
    falls back to flat-lane."""
    thresh = _I32_MAX if threshold is None else int(threshold)
    gid = jnp.asarray(np.asarray(gid, np.int32).reshape(-1, 1))
    if group_params is not None:
        group_params = jnp.asarray(np.asarray(group_params, np.int32))
    if not use_kernel:
        partials = _chunked_ref(
            store["data"], store["ts"], gid, member_ts, floor,
            tag_main, tag_alt, thresh, n_groups=n_groups,
            group_params=group_params)
    else:
        LAUNCH_STATS["pallas_calls"] += 2      # select + reduce
        partials = rss_scan_agg_chunked(
            store["data"], store["ts"], gid, member_ts, floor,
            tag_main, tag_alt, thresh, n_groups=n_groups,
            group_params=group_params, group_tile=group_tile,
            interpret=interpret)
    return np.asarray(tree_fold_partials(partials)).tolist()


# --- incremental delta fold (materialized aggregates) -----------------------

_delta_fold_ref_j = jax.jit(rss_delta_fold_ref)


def delta_fold(acc, delta, *, use_kernel: bool = True,
               interpret: Optional[bool] = None) -> jax.Array:
    """Advance a materialized-aggregate accumulator tile by a dense delta
    buffer: acc [Lp, 128] int32 lane rows (lanes 0..6 = sum, count,
    count_below, min, max, count_above, sum_below), delta [Dp, 128] int32
    change rows — col 0 = target lane (-1 = padding), 1 = retracted old
    value, 2 = old-valid, 3 = applied new value, 4 = new-valid, 5 =
    threshold.  O(delta) regardless of table size — this is the commit-
    time fold behind `tensorstore.materialized.MaterializedView`.  The
    caller owns the int32 overflow ladder (bounded |contribution| and
    bounded pending-buffer length); min/max lanes only tighten here —
    retracting an attained bound is the host's dirty-bit demotion."""
    acc = jnp.asarray(acc, jnp.int32)
    delta = jnp.asarray(delta, jnp.int32)
    LAUNCH_STATS["delta_folds"] += 1
    if not use_kernel:
        return _delta_fold_ref_j(acc, delta)
    LAUNCH_STATS["pallas_calls"] += 1
    return rss_delta_fold(acc, delta, interpret=interpret)


def grouped_agg_auto(store: dict, gid, n_groups: int, member_ts, floor=0,
                     *, group_params=None, n_plans: int = 1,
                     mode: Optional[str] = None,
                     maxabs: int,
                     use_kernel: bool = True,
                     interpret: Optional[bool] = None):
    """Shape-dispatched grouped aggregate: pick flat / chunked by
    (P, G, n_plans) — or honor `mode` / REPRO_GROUPED_MODE — run it, and
    return (rows, mode_used).  mode_used == "host" returns (None,
    "host"): the caller (the mirror) owns the decode-and-aggregate
    fallback, since it needs key-level values the kernel layer never
    sees.  A chunked pick that violates the whole-scan int32 bound
    silently demotes to flat (exact host fold) and counts an
    overflow_fallback.  `maxabs` as in `snapshot_agg_members`."""
    P = int(store["ts"].shape[0])
    m = select_grouped_mode(P, n_groups, n_plans, override=mode)
    if m == "chunked":
        if not scan_bound_ok(guard_maxabs(maxabs), P):
            LAUNCH_STATS["overflow_fallbacks"] += 1
            m = "flat"
    LAUNCH_STATS["dispatches"] += 1
    LAUNCH_STATS[m] += 1
    if m == "host":
        return None, m
    if m == "chunked":
        rows = snapshot_group_agg_chunked(
            store, gid, n_groups, member_ts, floor,
            group_params=group_params, use_kernel=use_kernel,
            interpret=interpret)
    else:
        rows = snapshot_group_agg_members(
            store, gid, n_groups, member_ts, floor,
            group_params=group_params, maxabs=maxabs,
            use_kernel=use_kernel, interpret=interpret)
    return rows, m
