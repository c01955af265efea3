"""Pallas TPU kernel: fused RSS visibility resolve + aggregate (scan+agg).

This is the device-resident OLAP executor's hot loop: one pass that resolves
RSS set-membership visibility for a key-range of pages per grid step (the
multi-page columnar extension of `rss_gather`'s one-slot-per-page resolve)
AND reduces the member-visible payloads on device — sum / count /
count-below-threshold / min / max / count-above-threshold /
sum-below-threshold over a tagged scalar field — so scan results never
leave the device.  The host receives seven scalars instead of P decoded
pages.

Contract (matches ref.py):
    data      [P, K, E] int32  page payloads; element 0 is the codec tag,
                               element 1 the aggregable field
                               (`tensorstore.mirror` codec)
    ts        [P, K]    int32  commit timestamp per slot (0 = initial)
    member_ts [M]       int32  sorted member commit timestamps ABOVE floor
    floor     scalar           compressed-snapshot watermark; with M == 0 it
                               degrades to prefix (SI-V) visibility, so the
                               same kernel serves watermark aggregates
    tag_main / tag_alt         payload tags that participate in the
                               aggregate (tag_alt = -2 to disable: real
                               tags are >= 0 and -1 marks sublane-padding
                               pages, so neither ever matches -2)
    threshold scalar           predicate bound shared by the thresholded
                               lanes (count_below / count_above /
                               sum_below)
    out       [P/BP, 7] int32  ONE PARTIAL ROW PER GRID BLOCK: sum, count,
                               count_below, min (INT32_MAX when the block
                               matched nothing), max (INT32_MIN),
                               count_above, sum_below

Visibility is the `rss_gather` protocol verbatim (ts <= floor OR ts in the
member array, newest wins, ties toward the lowest slot).  Each grid step
reduces its BP-page block to one partial row; `ops.snapshot_agg_members`
folds the rows ON HOST in arbitrary-precision Python ints.  Deliberate
overflow discipline: device arithmetic stays int32 (TPU-native), so a
whole-scan sum can exceed int32 without wrapping — only a single BP-page
block's partial must fit (|field| max < 2**31/BP per block; `ops` enforces
the bound host-side and takes an exact fallback when violated), keeping
the fused result bitwise equal to the per-key Python oracle.

TPU tiling: every block's last two dims are multiples of (8, 128) or the
array's own dims, so BP is a multiple of 8 and every output block holds
at least 8 rows.  The scalar aggregate is the flat grouped kernel with
one group (its [8, 128] tile carries the row in sublane 0).

Arithmetic intensity stays ~1 FLOP per K bytes read, but the fused path
writes P/BP partial rows instead of P·E gathered elements and skips the
host decode loop entirely — the win
`benchmarks.bench_kernels.scan_agg_report` measures.

Three grouped strategies (shape-dispatched by `ops.select_grouped_mode`):

`rss_scan_agg_grouped` — FLAT-LANE: every page carries a group id (`gid
[P, 1]`, -1 = no group), each grid step reduces its BP-page block into
PER-GROUP accumulator lanes — a [Gp, 128] tile whose row g holds group
g's [sum, count, count_below, min, max, count_above, sum_below] partial.
All G lanes stay live
every grid step, so VMEM pressure grows with G; fine for small group
counts, decays past G ~ 8-16.  Per-group kernel params (`group_params
[G, 3] = tag_main, tag_alt, threshold` rows) let ONE launch serve lanes
drawn from different plans/configs — the whole-batch fusion substrate.

`rss_scan_agg_chunked` — CHUNKED TWO-STAGE: stage one resolves
visibility ONCE and packs (tag, field, gid) for 64 pages per row into a
[rows, 256] intermediate (lanes 0-63 tag, 64-127 field, 128-191 gid,
192-255 zero), 8 rows (512 pages) per grid step; stage two re-reduces
that packed stream over a TILED group axis — grid (G/G_tile, chunks,
steps) where each step accumulates `rows_per_step` rows into its chunk's
[G_tile, 128] partial tile via `@pl.when` revisits.  VMEM per step is bounded by G_tile, not
G, so G=64..256 no longer falls off the cliff, and the expensive member
compare runs once instead of once per group tile.  The [chunks, G, 7]
partials fold to [G, 7] with `tree_fold_partials` ON DEVICE (pairwise,
int32) — exactness now needs the whole-scan bound |field| max <
2**31/P, which `ops` checks host-side, falling back to flat-lane (exact
host fold) when violated.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..config import resolve_interpret

_I32_MAX = jnp.iinfo(jnp.int32).max
_I32_MIN = jnp.iinfo(jnp.int32).min

# pages packed per select row: 64 tag + 64 field + 64 gid + 64 zero lanes
SELECT_BLOCK = 64
# rows per select grid step (and the unit of `rows_per_step`): the TPU's
# sublane tile, so no output block is thinner than 8 rows
SELECT_ROWS = 8


def _resolve_tag_x(mem_ref, scal_ref, ts_ref, data_ref):
    """Shared block body: RSS visibility resolve over one BP-page block.
    Returns (tag, x): the codec tag and aggregable field of each page's
    member-visible slot."""
    ts = ts_ref[...]                           # [BP, K] int32
    mem = mem_ref[...]                         # [1, Mp] int32 (-1 padded)
    floor = scal_ref[0, 0]
    # --- visibility resolve (rss_gather protocol) -----------------------
    is_member = (ts <= floor) | jnp.any(
        ts[:, :, None] == mem[0][None, None, :], axis=-1)
    masked = jnp.where(is_member, ts, -1)
    best = jnp.max(masked, axis=1, keepdims=True)          # [BP, 1]
    onehot = masked == best
    idx = jnp.arange(ts.shape[1], dtype=jnp.int32)[None, :]
    first = jnp.min(jnp.where(onehot, idx, ts.shape[1]), axis=1,
                    keepdims=True)
    onehot = idx == first                                  # [BP, K]
    data = data_ref[...]                                   # [BP, K, E]
    sel = jnp.sum(onehot.astype(data.dtype)[:, :, None] * data, axis=1)
    return sel[:, 0], sel[:, 1]                            # tag, x: [BP]


def _scal_tile(floor, tag_main, tag_alt, threshold):
    # scalar params as one lane-aligned [1, 128] tile (same idiom as the
    # rss_gather floor tile): [0]=floor, [1]=tag_main, [2]=tag_alt,
    # [3]=threshold
    scal = jnp.zeros((1, 128), jnp.int32)
    scal = scal.at[0, 0].set(jnp.asarray(floor, jnp.int32))
    scal = scal.at[0, 1].set(jnp.asarray(tag_main, jnp.int32))
    scal = scal.at[0, 2].set(jnp.asarray(tag_alt, jnp.int32))
    scal = scal.at[0, 3].set(jnp.asarray(threshold, jnp.int32))
    return scal


def _mem_tile(member_ts):
    M = member_ts.shape[0]
    mp = max(128, -(-M // 128) * 128)          # lane-aligned, >= 1 tile
    mem = jnp.full((1, mp), -1, jnp.int32)
    if M:
        mem = mem.at[0, :M].set(member_ts.astype(jnp.int32))
    return mem, mp


def _group_param_tile(n_groups, gp, tag_main, tag_alt, threshold,
                      group_params):
    """[Gp, 128] per-group kernel params: lane 0 tag_main, 1 tag_alt,
    2 threshold.  group_params=None broadcasts the scalar args to every
    group (classic single-config launch); a [n_groups, 3] array gives
    each accumulator lane its own config — the batch-fusion substrate.
    Padded group rows keep zeros: no page's gid ever maps to them."""
    if group_params is None:
        prm = jnp.stack([
            jnp.full((n_groups,), jnp.asarray(tag_main, jnp.int32)),
            jnp.full((n_groups,), jnp.asarray(tag_alt, jnp.int32)),
            jnp.full((n_groups,), jnp.asarray(threshold, jnp.int32)),
        ], axis=1)
    else:
        prm = jnp.asarray(group_params, jnp.int32)
    gtile = jnp.zeros((gp, 128), jnp.int32)
    gtile = gtile.at[:n_groups, 0].set(prm[:, 0])
    gtile = gtile.at[:n_groups, 1].set(prm[:, 1])
    gtile = gtile.at[:n_groups, 2].set(prm[:, 2])
    return gtile


@functools.partial(jax.jit, static_argnames=("block_pages", "interpret"))
def rss_scan_agg(data: jax.Array, ts: jax.Array, member_ts: jax.Array,
                 floor: jax.Array | int = 0,
                 tag_main: jax.Array | int = 1,
                 tag_alt: jax.Array | int = -2,
                 threshold: jax.Array | int = _I32_MAX,
                 *, block_pages: int = 8,
                 interpret: bool | None = None) -> jax.Array:
    """Fused RSS membership scan + aggregate; returns [P/BP, 7] int32
    per-block partials of [sum, count, count_below, min, max,
    count_above, sum_below] over member-visible payloads whose tag is
    tag_main or tag_alt (fold the block axis on host — lanes 0-2 and 5-6
    add, 3 min, 4 max).  Lowered onto the flat grouped kernel with one
    group, so the scalar and grouped aggregates share one kernel."""
    gid = jnp.zeros((data.shape[0], 1), jnp.int32)
    return rss_scan_agg_grouped(data, ts, gid, member_ts, floor, tag_main,
                                tag_alt, threshold, n_groups=1,
                                block_pages=block_pages,
                                interpret=interpret)[:, 0]


def _grouped_kernel(mem_ref, scal_ref, gprm_ref, gid_ref, ts_ref, data_ref,
                    out_ref):
    tag, x = _resolve_tag_x(mem_ref, scal_ref, ts_ref, data_ref)
    gid = gid_ref[...][:, 0]                               # [BP]
    prm = gprm_ref[...]                                    # [Gp, 128]
    gp = out_ref.shape[0]                                  # padded groups
    # page -> group one-hot; gid -1 (no group / padding) matches nothing,
    # and the tag test is PER GROUP LANE (lanes may carry distinct plan
    # configs in a fused batch launch)
    giota = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], gp), 1)
    tagm = ((tag[:, None] == prm[:, 0][None, :]) |
            (tag[:, None] == prm[:, 1][None, :]))
    grp = (gid[:, None] == giota) & tagm                   # [BP, Gp]
    thresh = prm[:, 2][None, :]                            # [1, Gp]
    xg = x[:, None]
    below = grp & (xg < thresh)
    psum = jnp.sum(jnp.where(grp, xg, 0), axis=0)          # [Gp]
    pcount = jnp.sum(grp.astype(jnp.int32), axis=0)
    pbelow = jnp.sum(below.astype(jnp.int32), axis=0)
    pmin = jnp.min(jnp.where(grp, xg, _I32_MAX), axis=0)
    pmax = jnp.max(jnp.where(grp, xg, _I32_MIN), axis=0)
    pabove = jnp.sum((grp & (xg > thresh)).astype(jnp.int32), axis=0)
    psumb = jnp.sum(jnp.where(below, xg, 0), axis=0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (gp, 128), 1)
    tile = jnp.where(lane == 0, psum[:, None], 0)
    tile = jnp.where(lane == 1, pcount[:, None], tile)
    tile = jnp.where(lane == 2, pbelow[:, None], tile)
    tile = jnp.where(lane == 3, pmin[:, None], tile)
    tile = jnp.where(lane == 4, pmax[:, None], tile)
    tile = jnp.where(lane == 5, pabove[:, None], tile)
    tile = jnp.where(lane == 6, psumb[:, None], tile)
    out_ref[...] = tile                        # this block's [Gp, 128] tile


@functools.partial(jax.jit, static_argnames=("n_groups", "block_pages",
                                             "interpret"))
def rss_scan_agg_grouped(data: jax.Array, ts: jax.Array, gid: jax.Array,
                         member_ts: jax.Array,
                         floor: jax.Array | int = 0,
                         tag_main: jax.Array | int = 1,
                         tag_alt: jax.Array | int = -2,
                         threshold: jax.Array | int = _I32_MAX,
                         *, n_groups: int = 1, block_pages: int = 8,
                         group_params: jax.Array | None = None,
                         interpret: bool | None = None) -> jax.Array:
    """Fused RSS membership scan + GROUPED aggregate (flat-lane): `gid` is
    a [P, 1] int32 group id per page (0..n_groups-1; -1 = no group,
    matching no accumulator lane — sublane padding).  Returns [P/BP,
    n_groups, 7] int32 per-block per-group partials of [sum, count,
    count_below, min, max, count_above, sum_below] over member-visible
    payloads whose tag matches the group's config (fold the block axis
    per group on host — lanes 0-2 and 5-6
    add, 3 min, 4 max).  group_params [n_groups, 3] int32 (tag_main,
    tag_alt, threshold per lane) overrides the scalar tag/threshold args
    per group, so one launch can serve lanes from different plans."""
    P, K, E = data.shape
    assert ts.shape == (P, K) and gid.shape == (P, 1)
    assert n_groups >= 1
    bp = min(block_pages, P)
    assert bp % 8 == 0 and P % bp == 0, (P, bp)
    gp = -(-n_groups // 8) * 8                 # sublane-aligned group rows
    mem, mp = _mem_tile(member_ts)
    scal = _scal_tile(floor, tag_main, tag_alt, threshold)
    gtile = _group_param_tile(n_groups, gp, tag_main, tag_alt, threshold,
                              group_params)
    out = pl.pallas_call(
        _grouped_kernel,
        grid=(P // bp,),
        in_specs=[
            pl.BlockSpec((1, mp), lambda i: (0, 0)),        # members
            pl.BlockSpec((1, 128), lambda i: (0, 0)),       # scalar params
            pl.BlockSpec((gp, 128), lambda i: (0, 0)),      # group params
            pl.BlockSpec((bp, 1), lambda i: (i, 0)),        # group ids
            pl.BlockSpec((bp, K), lambda i: (i, 0)),        # ts
            pl.BlockSpec((bp, K, E), lambda i: (i, 0, 0)),  # data
        ],
        # one [Gp, 128] per-group partial tile per grid block, stacked
        # along rows: block i owns rows [i*Gp, (i+1)*Gp)
        out_specs=pl.BlockSpec((gp, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((P // bp * gp, 128), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(mem, scal, gtile, gid.astype(jnp.int32), ts, data)
    return out.reshape(P // bp, gp, 128)[:, :n_groups, :7]


# ---------------------------------------------------------------------------
# chunked two-stage grouped reduction
# ---------------------------------------------------------------------------

def _select_kernel(mem_ref, scal_ref, gid_ref, ts_ref, data_ref, out_ref):
    """Stage one: resolve visibility for SELECT_ROWS * SELECT_BLOCK pages
    and pack (tag, field, gid) into SELECT_ROWS rows of 4*SELECT_BLOCK
    lanes — the expensive member compare runs exactly once per page,
    independent of G."""
    tag, x = _resolve_tag_x(mem_ref, scal_ref, ts_ref, data_ref)
    gid = gid_ref[...][:, 0]                               # [R*SB]
    rows = out_ref.shape[0]
    out_ref[...] = jnp.concatenate(
        [v.reshape(rows, SELECT_BLOCK)
         for v in (tag, x, gid, jnp.zeros_like(tag))], axis=1)


def _chunk_reduce_kernel(gprm_ref, sel_ref, out_ref):
    """Stage two: re-reduce the packed select stream over a TILED group
    axis.  Grid (G/GT, chunks, steps); each step folds `rows_per_step`
    select rows into its (chunk, group-tile) partial via @pl.when
    revisits, so live VMEM is one group tile — bounded by the group tile,
    not by G.  The select rows stay [R, SB] (the TPU cannot cast lanes
    into sublanes), so groups ride the leading axis: params [GT, 1, 128],
    masks [GT, R, SB], partials [GT, 1, 128]."""
    i = pl.program_id(2)                                   # step in chunk
    j = pl.program_id(0)                                   # group tile
    sb = SELECT_BLOCK
    blk = sel_ref[...]                                     # [R, 4*SB]
    tag = blk[:, 0:sb][None]                               # [1, R, SB]
    x = blk[:, sb:2 * sb][None]
    gid = blk[:, 2 * sb:3 * sb][None]
    prm = gprm_ref[...]                                    # [GT, 1, 128]
    gt = prm.shape[0]
    # global group ids covered by this tile
    gl = j * gt + jax.lax.broadcasted_iota(jnp.int32, (gt, 1, 1), 0)
    grp = (gid == gl) & ((tag == prm[:, :, 0:1]) |
                         (tag == prm[:, :, 1:2]))          # [GT, R, SB]
    thresh = prm[:, :, 2:3]
    below = grp & (x < thresh)

    def total(v, red=jnp.sum):                             # -> [GT, 1, 1]
        return red(red(v, axis=2, keepdims=True), axis=1, keepdims=True)

    stats = (total(jnp.where(grp, x, 0)),
             total(grp.astype(jnp.int32)),
             total(below.astype(jnp.int32)),
             total(jnp.where(grp, x, _I32_MAX), jnp.min),
             total(jnp.where(grp, x, _I32_MIN), jnp.max),
             total((grp & (x > thresh)).astype(jnp.int32)),
             total(jnp.where(below, x, 0)))
    lane = jax.lax.broadcasted_iota(jnp.int32, (gt, 1, 128), 2)
    tile = jnp.zeros((gt, 1, 128), jnp.int32)
    for k, v in enumerate(stats):
        tile = jnp.where(lane == k, v, tile)

    @pl.when(i == 0)
    def _init():
        out_ref[0] = tile

    @pl.when(i > 0)
    def _accumulate():
        prev = out_ref[0]
        out_ref[0] = jnp.where(
            (lane < 3) | (lane >= 5), prev + tile,
            jnp.where(lane == 3, jnp.minimum(prev, tile),
                      jnp.maximum(prev, tile)))


def _chunk_shape(P: int, rows_per_step: int, fold_chunks: int):
    """Static chunking math shared by kernel and ref: pad P to
    rows * SELECT_BLOCK pages where rows divides evenly into
    `fold_chunks`-or-fewer chunks of `rows_per_step`-row steps
    (`rows_per_step` a multiple of SELECT_ROWS)."""
    assert rows_per_step >= SELECT_ROWS and \
        rows_per_step % SELECT_ROWS == 0, rows_per_step
    sb = SELECT_BLOCK
    rows0 = max(1, -(-P // sb))
    r = rows_per_step
    nc = max(1, min(fold_chunks, rows0 // r))
    unit = r * nc
    rows = -(-rows0 // unit) * unit
    return rows, r, nc, rows * sb


def _pad_pages(data, ts, gid, P, Pp):
    """Pad to the chunk-aligned page count: tag -1 / ts 0 / gid -1 pages
    that match no group lane."""
    if Pp == P:
        return data, ts, gid.astype(jnp.int32)
    pad = Pp - P
    K, E = data.shape[1], data.shape[2]
    pad_data = jnp.zeros((pad, K, E), jnp.int32).at[:, :, 0].set(-1)
    data = jnp.concatenate([data, pad_data])
    ts = jnp.concatenate([ts, jnp.zeros((pad, K), jnp.int32)])
    gid = jnp.concatenate(
        [gid.astype(jnp.int32), jnp.full((pad, 1), -1, jnp.int32)])
    return data, ts, gid


@functools.partial(jax.jit, static_argnames=(
    "n_groups", "group_tile", "rows_per_step", "fold_chunks", "interpret"))
def rss_scan_agg_chunked(data: jax.Array, ts: jax.Array, gid: jax.Array,
                         member_ts: jax.Array,
                         floor: jax.Array | int = 0,
                         tag_main: jax.Array | int = 1,
                         tag_alt: jax.Array | int = -2,
                         threshold: jax.Array | int = _I32_MAX,
                         *, n_groups: int = 1,
                         group_params: jax.Array | None = None,
                         group_tile: int = 8,
                         rows_per_step: int = 8,
                         fold_chunks: int = 8,
                         interpret: bool | None = None) -> jax.Array:
    """Chunked two-stage grouped scan+agg: one select pass packs
    (tag, field, gid) per page, then a tiled-group reduce re-reads the
    packed stream — VMEM bounded by `group_tile`, visibility resolved
    once.  Returns [chunks, n_groups, 7] int32 per-chunk per-group
    partials (fold with `tree_fold_partials` on device, or
    `ops.fold_group_partials` on host).  Same lane semantics and
    group_params contract as `rss_scan_agg_grouped`; exact only when the
    whole-scan sum fits int32 (|field| max < 2**31/P — callers go through
    `ops`, which enforces the bound and falls back to flat-lane)."""
    P, K, E = data.shape
    assert ts.shape == (P, K) and gid.shape == (P, 1)
    assert n_groups >= 1
    assert group_tile >= 8 and group_tile % 8 == 0, group_tile
    sb = SELECT_BLOCK
    rows, r, nc, Pp = _chunk_shape(P, rows_per_step, fold_chunks)
    data, ts, gid = _pad_pages(data, ts, gid, P, Pp)
    gp = -(-n_groups // group_tile) * group_tile
    mem, mp = _mem_tile(member_ts)
    scal = _scal_tile(floor, tag_main, tag_alt, threshold)
    gtile = _group_param_tile(n_groups, gp, tag_main, tag_alt, threshold,
                              group_params)
    sr = SELECT_ROWS
    interpret = resolve_interpret(interpret)
    sel = pl.pallas_call(
        _select_kernel,
        grid=(rows // sr,),
        in_specs=[
            pl.BlockSpec((1, mp), lambda i: (0, 0)),        # members
            pl.BlockSpec((1, 128), lambda i: (0, 0)),       # scalar params
            pl.BlockSpec((sr * sb, 1), lambda i: (i, 0)),   # group ids
            pl.BlockSpec((sr * sb, K), lambda i: (i, 0)),   # ts
            pl.BlockSpec((sr * sb, K, E), lambda i: (i, 0, 0)),  # data
        ],
        out_specs=pl.BlockSpec((sr, 4 * sb), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, 4 * sb), jnp.int32),
        interpret=interpret,
    )(mem, scal, gid, ts, data)
    ngt = gp // group_tile
    bpc = rows // (r * nc)                     # steps per chunk
    out = pl.pallas_call(
        _chunk_reduce_kernel,
        grid=(ngt, nc, bpc),
        in_specs=[
            pl.BlockSpec((group_tile, 1, 128), lambda j, c, i: (j, 0, 0)),
            pl.BlockSpec((r, 4 * sb), lambda j, c, i: (c * bpc + i, 0)),
        ],
        out_specs=pl.BlockSpec((1, group_tile, 1, 128),
                               lambda j, c, i: (c, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nc, gp, 1, 128), jnp.int32),
        interpret=interpret,
    )(gtile[:, None, :], sel)
    return out[:, :n_groups, 0, :7]


# ---------------------------------------------------------------------------
# incremental delta fold (materialized aggregates)
# ---------------------------------------------------------------------------

def _delta_fold_kernel(acc_ref, delta_ref, out_ref):
    """Fold a dense delta buffer of changed rows into a live accumulator
    tile.  acc [Lp, 128]: one row per accumulator lane, lanes 0..6 =
    [sum, count, count_below, min, max, count_above, sum_below].  delta
    [Dp, 128]: one row per (key, lane) change, cols 0 = target lane (-1 =
    padding, folds nowhere), 1 = retracted old value, 2 = old-valid, 3 =
    applied new value, 4 = new-valid, 5 = threshold.  Version supersession
    is retract-then-apply: every additive stat subtracts the old
    contribution and adds the new one; min/max only TIGHTEN (they are not
    subtractable — the host owns the dirty-bit demotion ladder when a
    retracted value was the attained bound)."""
    acc = acc_ref[...]                                     # [Lp, 128]
    blk = delta_ref[...]                                   # [Dp, 128]
    lp = acc.shape[0]
    tgt = blk[:, 0]
    old, ov = blk[:, 1], blk[:, 2]
    new, nv = blk[:, 3], blk[:, 4]
    thr = blk[:, 5]
    onehot = tgt[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (blk.shape[0], lp), 1)                  # [Dp, Lp]
    oh = onehot.astype(jnp.int32)
    old_b = (old < thr).astype(jnp.int32)
    new_b = (new < thr).astype(jnp.int32)
    d_sum = new * nv - old * ov
    d_count = nv - ov
    d_below = nv * new_b - ov * old_b
    d_above = (nv * (new > thr).astype(jnp.int32)
               - ov * (old > thr).astype(jnp.int32))
    d_sumb = new * nv * new_b - old * ov * old_b
    s_sum = jnp.sum(oh * d_sum[:, None], axis=0)           # [Lp]
    s_count = jnp.sum(oh * d_count[:, None], axis=0)
    s_below = jnp.sum(oh * d_below[:, None], axis=0)
    s_above = jnp.sum(oh * d_above[:, None], axis=0)
    s_sumb = jnp.sum(oh * d_sumb[:, None], axis=0)
    cand = jnp.where(nv == 1, new, 0)
    s_min = jnp.min(jnp.where(onehot & (nv[:, None] == 1),
                              cand[:, None], _I32_MAX), axis=0)
    s_max = jnp.max(jnp.where(onehot & (nv[:, None] == 1),
                              cand[:, None], _I32_MIN), axis=0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (lp, 128), 1)
    out = jnp.where(lane == 0, acc + s_sum[:, None], acc)
    out = jnp.where(lane == 1, acc + s_count[:, None], out)
    out = jnp.where(lane == 2, acc + s_below[:, None], out)
    out = jnp.where(lane == 3, jnp.minimum(acc, s_min[:, None]), out)
    out = jnp.where(lane == 4, jnp.maximum(acc, s_max[:, None]), out)
    out = jnp.where(lane == 5, acc + s_above[:, None], out)
    out = jnp.where(lane == 6, acc + s_sumb[:, None], out)
    out_ref[...] = out


@functools.partial(jax.jit, static_argnames=("interpret",))
def rss_delta_fold(acc: jax.Array, delta: jax.Array, *,
                   interpret: bool | None = None) -> jax.Array:
    """Advance a materialized-aggregate accumulator tile by a dense delta
    buffer: acc [Lp, 128] int32 (lane rows, sublane-aligned), delta
    [Dp, 128] int32 change rows (see `_delta_fold_kernel` for the column
    layout; rows with col 0 == -1 are padding and fold nowhere).  Returns
    the advanced [Lp, 128] tile — O(delta) work, independent of table
    size.  int32 throughout: callers bound |contribution| and the pending
    buffer length so neither a row delta nor an additive accumulator lane
    can wrap (the `tensorstore.materialized` overflow ladder).  The whole
    buffer is one block, and the TPU compile time grows steeply with Dp
    (for a v5e: about a second at 1,024 rows, unfinished after 15
    minutes at 4,096), so callers keep Dp small
    (`materialized.FLUSH_ROWS`)."""
    lp, dp = acc.shape[0], delta.shape[0]
    assert acc.shape == (lp, 128) and delta.shape == (dp, 128)
    assert lp % 8 == 0 and dp % 8 == 0, (lp, dp)
    return pl.pallas_call(
        _delta_fold_kernel,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((lp, 128), lambda i: (0, 0)),     # accumulator
            pl.BlockSpec((dp, 128), lambda i: (0, 0)),     # delta rows
        ],
        out_specs=pl.BlockSpec((lp, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((lp, 128), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(acc, delta)


@jax.jit
def tree_fold_partials(partials: jax.Array) -> jax.Array:
    """Device-side pairwise fold of [chunks, G, 7] chunked partials into
    the final [G, 7] rows (lanes 0-2 and 5-6 add, 3 min, 4 max).  int32
    throughout — exact only under the whole-scan bound the chunked path
    already requires."""
    ident = jnp.asarray([0, 0, 0, _I32_MAX, _I32_MIN, 0, 0], jnp.int32)
    lane = jnp.arange(7, dtype=jnp.int32)[None, None, :]
    while partials.shape[0] > 1:
        if partials.shape[0] % 2:
            pad = jnp.broadcast_to(ident, (1,) + partials.shape[1:])
            partials = jnp.concatenate([partials, pad])
        a, b = partials[0::2], partials[1::2]
        partials = jnp.where(
            (lane < 3) | (lane >= 5), a + b,
            jnp.where(lane == 3, jnp.minimum(a, b), jnp.maximum(a, b)))
    return partials[0]
