"""Pallas TPU kernel: SI-V snapshot visibility resolution + page gather.

Contract (matches ref.py):
    data [P, K, E]   page payloads, K version slots per page
    ts   [P, K]      int32 commit timestamp per slot (0 = initial version)
    watermark        scalar int32 snapshot horizon
    out  [P, E]      payload of the newest slot with ts <= watermark

TPU adaptation of the paper's tuple-visibility walk: pages are blocked into
VMEM tiles; slot selection is a masked arg-max over the K (small) slot axis
done as a one-hot reduction so it vectorizes on the VPU — no per-page scalar
loop, no HBM round-trips beyond the single streaming read of `data`.

Block shapes: (BP pages × K slots × BE elems); BE is lane-aligned (128) and
BP sublane-aligned (8).  The slot one-hot multiply-add reads K·BP·BE elems
and writes BP·BE — the kernel is purely memory-bound (arithmetic intensity
≈ 1 FLOP / K·bytes), so the roofline target is HBM bandwidth.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..config import resolve_interpret


def _kernel(wm_ref, ts_ref, data_ref, out_ref):
    ts = ts_ref[...]                         # [BP, K] int32
    wm = wm_ref[0]
    masked = jnp.where(ts <= wm, ts, -1)     # invisible slots -> -1
    best = jnp.max(masked, axis=1, keepdims=True)        # [BP, 1]
    onehot = (masked == best)                            # [BP, K] bool
    # break ties toward the lowest slot index (unique ts makes this moot,
    # but the kernel must be deterministic regardless)
    idx = jnp.arange(ts.shape[1], dtype=jnp.int32)[None, :]
    first = jnp.min(jnp.where(onehot, idx, ts.shape[1]), axis=1,
                    keepdims=True)
    onehot = (idx == first)
    data = data_ref[...]                     # [BP, K, BE]
    sel = onehot.astype(data.dtype)[:, :, None] * data
    out_ref[...] = jnp.sum(sel, axis=1)


@functools.partial(jax.jit, static_argnames=("block_pages", "block_elems",
                                             "interpret"))
def version_gather(data: jax.Array, ts: jax.Array, watermark: jax.Array,
                   *, block_pages: int = 8, block_elems: int = 512,
                   interpret: bool | None = None) -> jax.Array:
    """Pallas snapshot read.  interpret=None resolves from the backend
    (`repro.kernels.config`): compiled on TPU, interpreted elsewhere."""
    P, K, E = data.shape
    assert ts.shape == (P, K)
    bp = min(block_pages, P)
    be = min(block_elems, E)
    assert P % bp == 0 and E % be == 0, (P, bp, E, be)
    wm = jnp.asarray(watermark, jnp.int32).reshape(1)
    grid = (P // bp, E // be)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i, j: (0,)),            # watermark
            pl.BlockSpec((bp, K), lambda i, j: (i, 0)),       # ts
            pl.BlockSpec((bp, K, be), lambda i, j: (i, 0, j)),  # data
        ],
        out_specs=pl.BlockSpec((bp, be), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((P, E), data.dtype),
        interpret=resolve_interpret(interpret),
    )(wm, ts, data)
