"""Public op: snapshot_read_members — Pallas kernel or jnp fallback."""

from __future__ import annotations

from typing import Optional

import jax

from .kernel import rss_gather
from .ref import rss_gather_ref


def snapshot_read_members(store: dict, member_ts, floor=0, *,
                          use_kernel: bool = True,
                          interpret: Optional[bool] = None) -> jax.Array:
    """RSS membership read over a paged store {'data': [P,K,E], 'ts': [P,K]}.

    member_ts is the sorted int32 array of member commit timestamps ABOVE
    the snapshot floor (the commit-seq image of an exported `RssSnapshot`:
    `snap.member_seqs` + `snap.floor_seq`); every version at ts <= floor is
    a floor-covered member's.  interpret=None resolves from the backend
    (`repro.kernels.config`): compiled on TPU, interpret mode elsewhere."""
    if not use_kernel:
        return rss_gather_ref(store["data"], store["ts"], member_ts, floor)
    return rss_gather(store["data"], store["ts"], member_ts, floor,
                      interpret=interpret)
