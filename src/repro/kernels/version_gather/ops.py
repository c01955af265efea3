"""Public op: snapshot_read — dispatches Pallas kernel or jnp fallback."""

from __future__ import annotations

from typing import Optional

import jax

from .kernel import version_gather
from .ref import version_gather_ref


def snapshot_read(store: dict, watermark, *, use_kernel: bool = True,
                  interpret: Optional[bool] = None) -> jax.Array:
    """SI-V read over a paged store {'data': [P,K,E], 'ts': [P,K]}.

    interpret=None resolves from the backend (`repro.kernels.config`):
    compiled on TPU, interpret mode elsewhere."""
    if not use_kernel:
        return version_gather_ref(store["data"], store["ts"], watermark)
    return version_gather(store["data"], store["ts"], watermark,
                          interpret=interpret)
