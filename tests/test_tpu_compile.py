"""Ahead-of-time compiles of the serve-path kernels for a described v5e.

The TPU compiler is installed even where no chip is attached: each case
lowers one kernel with `interpret=False` against a `v5e:2x2` topology
and compiles it for one chip, so a block shape the chip's tiling rules
refuse, or a kernel that outgrows its fast memory, fails here instead of
on the chip.  Shapes are the real ones: one TPC-C warehouse is about
130k one-KiB pages (K=8 slots of E=32 int32 elements).  Nothing runs,
so these cases say nothing about results or times.

The topology is described inside a module-scoped fixture (never at
import): only one process may load the TPU library, and every test
worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.rss_scan_agg import kernel as k
from repro.kernels.rss_scan_agg.ops import BLOCK_PAGES
from repro.mvcc.workload import Scale

K, E = 8, 32                 # slots per page, int32 elements per slot
# one TPC-C warehouse's reserved keys, sublane-padded: 130,096 pages
WAREHOUSE_PAGES = -(-len(Scale(warehouses=1, districts=10, customers=3000,
                               items=100000).key_families()) // 8) * 8


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("P", [8, WAREHOUSE_PAGES, 131072])
@pytest.mark.parametrize("M", [0, 2000])
def test_scalar_compiles(one_chip, P, M):
    """The scalar aggregate at the ladder's only block size (8 pages),
    with no members and with a member array many lane tiles wide."""
    _compile(one_chip,
             lambda d, t, m: k.rss_scan_agg(d, t, m, 3, 1, -2, 100,
                                            block_pages=BLOCK_PAGES,
                                            interpret=False),
             (P, K, E), (P, K), (M,))


@pytest.mark.parametrize("G", [1, 16, 32])
def test_flat_grouped_compiles(one_chip, G):
    P = WAREHOUSE_PAGES
    _compile(one_chip,
             lambda d, t, g, m: k.rss_scan_agg_grouped(
                 d, t, g, m, 3, n_groups=G, block_pages=BLOCK_PAGES,
                 interpret=False),
             (P, K, E), (P, K), (P, 1), (16,))


@pytest.mark.parametrize("P", [8, 72, WAREHOUSE_PAGES, 131072])
@pytest.mark.parametrize("G", [64, 256])
def test_chunked_grouped_compiles(one_chip, P, G):
    """Chunked two-stage path past the flat-lane limit (G > 32): the
    select pass writes 8 packed rows per step; small stores pad up."""
    _compile(one_chip,
             lambda d, t, g, m: k.rss_scan_agg_chunked(
                 d, t, g, m, 3, n_groups=G, interpret=False),
             (P, K, E), (P, K), (P, 1), (16,))


@pytest.mark.parametrize("lanes,deltas", [(8, 8), (64, 256)])
def test_delta_fold_compiles(one_chip, lanes, deltas):
    _compile(one_chip, lambda a, d: k.rss_delta_fold(a, d, interpret=False),
             (lanes, 128), (deltas, 128))
