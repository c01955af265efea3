"""Record the small device trace that `test_bench_trace.py` reads.

    python bench/tests/record_trace.py <out_dir>

Run on one TPU chip.  Inside a `bench:window` annotation it runs the fused
scan kernel (scalar and flat grouped) on a 64-page store between two
`bench:flush` annotations, with an unannotated host sleep in between (an
idle gap labelled "other/-"), and copies the `.xplane.pb` to
`<out_dir>/scan_window.xplane.pb`.  It prints the trace's planes, lines
and distinct device op names.
"""

from __future__ import annotations

import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    from bench.trace_reduce import find_xplane
    from repro.kernels.rss_scan_agg.kernel import (rss_scan_agg,
                                                   rss_scan_agg_grouped)

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1
    P, K, E = 64, 8, 32
    data = jnp.ones((P, K, E), jnp.int32)
    ts = jnp.tile(jnp.arange(K, dtype=jnp.int32)[None, :], (P, 1))
    mem = jnp.asarray([3, 5], jnp.int32)
    gid = (jnp.arange(P, dtype=jnp.int32) % 4)[:, None]
    jax.block_until_ready(rss_scan_agg(data, ts, mem, 2))
    jax.block_until_ready(rss_scan_agg_grouped(data, ts, gid, mem, 2,
                                               n_groups=4))
    tmp = tempfile.mkdtemp(prefix="record_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench:window"):
        with jax.profiler.TraceAnnotation("bench:flush"):
            jax.block_until_ready(rss_scan_agg(data, ts, mem, 2))
        time.sleep(0.02)
        with jax.profiler.TraceAnnotation("bench:flush"):
            jax.block_until_ready(rss_scan_agg_grouped(
                data, ts, gid, mem, 2, n_groups=4))
    jax.profiler.stop_trace()
    src = find_xplane(tmp)
    dst = Path(out_dir) / "scan_window.xplane.pb"
    dst.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(str(dst)).planes:
        names = {}
        for line in plane.lines:
            for e in line.events:
                names.setdefault(line.name, set()).add(e.name)
        print(plane.name, {ln: sorted(n)[:40] for ln, n in names.items()
                           if not plane.name.startswith("/host")})
    print(f"wrote {dst} ({dst.stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
