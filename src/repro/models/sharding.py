"""Activation-sharding hints that degrade gracefully off-mesh.

Model code calls `hint(x, "data", None, "model")`-style constraints; when no
mesh is active (CPU smoke tests) or a dimension is not divisible by its mesh
axis, the hint is skipped for that dim.  Under `with_mesh(mesh)` (used by the
launcher and dry-run) hints become real `with_sharding_constraint`s that GSPMD
propagates.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_MESH: contextvars.ContextVar[Optional[Mesh]] = \
    contextvars.ContextVar("repro_mesh", default=None)

# logical -> physical axis mapping; "data" may map to ("pod","data") multi-pod
_AXIS_MAP: contextvars.ContextVar[dict] = \
    contextvars.ContextVar("repro_axis_map", default={})


@contextlib.contextmanager
def with_mesh(mesh: Mesh, axis_map: Optional[dict] = None):
    """Activate a mesh for model-internal sharding hints."""
    amap = axis_map or {}
    tok1 = _MESH.set(mesh)
    tok2 = _AXIS_MAP.set(amap)
    try:
        with jax.set_mesh(mesh):
            yield mesh
    finally:
        _MESH.reset(tok1)
        _AXIS_MAP.reset(tok2)


def current_mesh() -> Optional[Mesh]:
    return _MESH.get()


def resolve_axis(logical: Optional[str]):
    """Map a logical axis name to physical mesh axis (or tuple)."""
    if logical is None:
        return None
    return _AXIS_MAP.get().get(logical, logical)


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def hint(x: jax.Array, *spec):
    """Best-effort sharding constraint; skips non-divisible dims / no mesh."""
    mesh = _MESH.get()
    if mesh is None:
        return x
    resolved = []
    for dim, axis in zip(x.shape, spec):
        phys = resolve_axis(axis)
        if phys is None or dim % _axis_size(mesh, phys) != 0:
            resolved.append(None)
        else:
            resolved.append(phys)
    try:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*resolved)))
    except Exception:
        return x
