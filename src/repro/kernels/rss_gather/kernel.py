"""Pallas TPU kernel: RSS set-membership visibility resolution + page gather.

Contract (matches ref.py and `tensorstore.paged.visible_slots_members`):
    data      [P, K, E]  page payloads, K version slots per page
    ts        [P, K]     int32 commit timestamp per slot (0 = initial version)
    member_ts [M]        sorted int32 commit timestamps of RSS members
                         ABOVE the snapshot floor
    floor     scalar     compressed-snapshot watermark: every committed
                         version at ts <= floor belongs to a member
                         (0 = no floor: initial versions only)
    out       [P, E]     payload of the newest slot whose ts is <= floor
                         or a member

The floor keeps the member array bounded by the concurrent transaction
window instead of growing with history — the kernel-side half of the
incremental-RSS compressed snapshot export.

This is the RSS read protocol of the paper vectorized for TPU: instead of a
prefix watermark (`version_gather`), visibility is membership in the exported
snapshot set — the previous-version read that skips committed-but-not-member
writers.  Same block/VMEM tiling discipline as `version_gather`: pages are
blocked into VMEM tiles, slot selection is a masked arg-max over the small K
axis via a one-hot reduction (VPU-friendly, no scalar loops).

Membership is a broadcast compare against the member array, padded to a
lane-aligned [1, Mp] tile with -1 sentinels (valid commit-ts are >= 0, so
padding never matches).  An EMPTY member set (M == 0) therefore degenerates
to the ts == 0 test alone and resolves every page to its initial slot — the
empty-RSS edge case the jnp searchsorted formulation got wrong.

Arithmetic intensity ≈ (K·M compares + K FMA) per K·E-byte page read — still
memory-bound for realistic M, so the roofline target stays HBM bandwidth.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..config import resolve_interpret


def _kernel(mem_ref, floor_ref, ts_ref, data_ref, out_ref):
    ts = ts_ref[...]                           # [BP, K] int32
    mem = mem_ref[...]                         # [1, Mp] int32 (-1 padded)
    floor = floor_ref[0, 0]                    # scalar watermark
    is_member = (ts <= floor) | jnp.any(
        ts[:, :, None] == mem[0][None, None, :], axis=-1)
    masked = jnp.where(is_member, ts, -1)      # non-member slots -> -1
    best = jnp.max(masked, axis=1, keepdims=True)          # [BP, 1]
    onehot = masked == best                                # [BP, K] bool
    # deterministic tie-break toward the lowest slot index (matches the
    # argmax-first semantics of the jnp oracle)
    idx = jnp.arange(ts.shape[1], dtype=jnp.int32)[None, :]
    first = jnp.min(jnp.where(onehot, idx, ts.shape[1]), axis=1,
                    keepdims=True)
    onehot = idx == first
    data = data_ref[...]                       # [BP, K, BE]
    sel = onehot.astype(data.dtype)[:, :, None] * data
    out_ref[...] = jnp.sum(sel, axis=1)


@functools.partial(jax.jit, static_argnames=("block_pages", "block_elems",
                                             "interpret"))
def rss_gather(data: jax.Array, ts: jax.Array, member_ts: jax.Array,
               floor: jax.Array | int = 0,
               *, block_pages: int = 8, block_elems: int = 512,
               interpret: bool | None = None) -> jax.Array:
    """Pallas RSS membership read.  interpret=None resolves from the
    backend (`repro.kernels.config`): compiled on TPU, interpreted
    elsewhere."""
    P, K, E = data.shape
    assert ts.shape == (P, K)
    bp = min(block_pages, P)
    be = min(block_elems, E)
    assert P % bp == 0 and E % be == 0, (P, bp, E, be)
    M = member_ts.shape[0]
    mp = max(128, -(-M // 128) * 128)          # lane-aligned, >= 1 tile
    mem = jnp.full((1, mp), -1, jnp.int32)
    if M:
        mem = mem.at[0, :M].set(member_ts.astype(jnp.int32))
    # scalar floor as a lane-aligned [1, 128] tile (same idiom as members;
    # valid commit-ts are >= 0 so the kernel only reads element [0, 0])
    floor_tile = jnp.full((1, 128), jnp.asarray(floor, jnp.int32))
    grid = (P // bp, E // be)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, mp), lambda i, j: (0, 0)),       # members
            pl.BlockSpec((1, 128), lambda i, j: (0, 0)),      # floor
            pl.BlockSpec((bp, K), lambda i, j: (i, 0)),       # ts
            pl.BlockSpec((bp, K, be), lambda i, j: (i, 0, j)),  # data
        ],
        out_specs=pl.BlockSpec((bp, be), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((P, E), data.dtype),
        interpret=resolve_interpret(interpret),
    )(mem, floor_tile, ts, data)
