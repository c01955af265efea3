"""Production mesh construction.

Single pod: 16×16 = 256 chips, axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model") — the "pod"
axis is the HTAP boundary (OLTP/training pod 0 ships its WAL to the
OLAP/serving pod 1 asynchronously); for training dry-runs it acts as an
outer data-parallel axis so the full 512-chip lowering is exercised.

Functions, not module constants: importing this module never touches JAX
device state (the dry-run must set XLA_FLAGS before any device query).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Tiny mesh over the locally available devices (CPU smoke tests)."""
    n = len(jax.devices())
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def dp_axes(mesh) -> tuple:
    """Axes batch is sharded over (pod absorbs into data-parallel)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def train_dp_axes(mesh, cfg) -> tuple:
    """Batch axes for training: fsdp2d folds the model axis into data
    parallelism when the global batch divides the full chip count."""
    base = dp_axes(mesh)
    if getattr(cfg, "train_sharding", "tp") == "fsdp2d" \
            and "pod" not in mesh.axis_names:
        return base + ("model",)
    return base


def dp_size(mesh) -> int:
    n = 1
    for a in dp_axes(mesh):
        n *= mesh.shape[a]
    return n
