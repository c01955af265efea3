"""repro: Serializable HTAP with Abort-/Wait-free Snapshot Read (RSS),
reproduced with OLTP on the host and OLAP in Pallas kernels on the TPU.

Subpackages:
  core        the paper's contribution (RSS theory, Algorithm 1, SSI, WAL)
  mvcc        executable MVCC engine + HTAP architectures + CH-benchmark
  cluster     N-way WAL fan-out replica cluster + lag-aware RSS routing
  tensorstore paged mirror, plan IR and materialized views over it
  kernels     Pallas TPU kernels + jnp oracles
  obs         metric registry and profiler-visible spans
"""

__version__ = "1.0.0"
