#!/usr/bin/env python3
"""Idle device time by the program span the host was in.

    python scripts/span_idle.py --workload <cell> --seed <n> [--seconds 30] \
        [--out .span_traces]
    python scripts/span_idle.py --xplane <trace.xplane.pb>

The first form runs one benchmark cell as `bench/run.py --trace 1` does (on
a TPU), keeps its profiler trace as `<out>/<cell>.<seed>.xplane.pb`, and
prints the run's result line.  Both forms then print one JSON line: the
device's busy and idle seconds inside the benchmark's `bench:window`
annotation, its idle seconds by `<phase>/<span>`, where `<phase>` is the
harness's `bench:` phase and `<span>` the innermost program span (`repro:`,
from `repro.obs.span`) open on the host at each idle gap's midpoint ("-"
where none was), and each program span's count and host seconds in the
window.
"""

from __future__ import annotations

import argparse
import io
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

BENCH_PREFIX, SPAN_PREFIX = "bench:", "repro:"


def _innermost(spans: list, points: list) -> list:
    """For each point (ascending), the name of the innermost span covering
    it, or None; `spans` are (start, end, name) and nest or follow one
    another, as annotations of one thread do."""
    spans = sorted(spans, key=lambda s: (s[0], -s[1]))
    out, stack, i = [], [], 0
    for t in points:
        while i < len(spans) and spans[i][0] <= t:
            while stack and stack[-1][1] <= spans[i][0]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] <= t:
            stack.pop()
        out.append(stack[-1][2] if stack else None)
    return out


def idle_by_span(path: str) -> dict:
    """Reduce a `.xplane.pb` to idle device seconds by phase and span."""
    from jax.profiler import ProfileData

    from bench.trace_reduce import _CHIP, _device_lines, _union

    pd = ProfileData.from_file(path)
    phases, spans, devices = [], [], []
    for plane in pd.planes:
        if _CHIP.match(plane.name):
            evs = [(e.start_ns, e.start_ns + e.duration_ns)
                   for ln in _device_lines(plane) for e in ln.events]
            if evs:
                devices.append(evs)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    ev = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    if e.name.startswith(BENCH_PREFIX):
                        phases.append(ev)
                    elif e.name.startswith(SPAN_PREFIX):
                        spans.append(ev)
    win = [(s, e) for s, e, n in phases if n == BENCH_PREFIX + "window"]
    if not win:
        raise ValueError(f"trace has no {BENCH_PREFIX}window annotation")
    w0, w1 = win[0]
    phases = [p for p in phases if p[2] != BENCH_PREFIX + "window"]
    gaps, busy = [], 0.0
    for evs in devices:
        merged = _union([(max(s, w0), min(e, w1)) for s, e in evs
                         if e > w0 and s < w1])
        busy += sum(e - s for s, e in merged)
        t = w0
        for s, e in merged + [[w1, w1]]:
            if s > t:
                gaps.append(((s + t) / 2, s - t))
            t = max(t, e)
    gaps.sort()
    mids = [m for m, _ in gaps]
    by: dict = {}
    for (_m, dur), ph, sp in zip(gaps, _innermost(phases, mids),
                                 _innermost(spans, mids)):
        key = (f"{ph[len(BENCH_PREFIX):] if ph else 'other'}/"
               f"{sp[len(SPAN_PREFIX):] if sp else '-'}")
        by[key] = by.get(key, 0.0) + dur * 1e-9
    host: dict = {}
    for s, e, name in spans:
        if w0 <= s and e <= w1:
            ent = host.setdefault(name[len(SPAN_PREFIX):], [0, 0.0])
            ent[0] += 1
            ent[1] += (e - s) * 1e-9
    n_dev = max(len(devices), 1)
    return {"window_s": (w1 - w0) * 1e-9, "busy_s": busy * 1e-9 / n_dev,
            "n_devices": len(devices), "repro_spans": len(spans),
            "idle_s_by_span": dict(sorted(by.items(),
                                          key=lambda kv: -kv[1])),
            "span_count_s": dict(sorted(host.items(),
                                        key=lambda kv: -kv[1][1]))}


def run_kept(workload: str, seed: int, seconds: float, out: Path) -> Path:
    """One traced run of the cell through `bench/run.py`, its trace kept."""
    import bench.run
    import bench.trace_reduce

    out.mkdir(parents=True, exist_ok=True)
    kept = out / f"{workload}.{seed}.xplane.pb"
    reduce = bench.trace_reduce.reduce_trace

    def keep_then_reduce(path, **kw):
        shutil.copy(path, kept)
        return reduce(path, **kw)

    bench.trace_reduce.reduce_trace = keep_then_reduce
    args = bench.run.parse_args(["--workload", workload, "--seed", str(seed),
                                 "--seconds", str(seconds), "--trace", "1"])
    buf = io.StringIO()
    rc = bench.run.run_cell(args, out=buf)
    print(buf.getvalue(), end="")
    if rc:
        raise SystemExit(rc)
    return kept


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--out", default=".span_traces")
    ap.add_argument("--xplane")
    a = ap.parse_args(argv)
    if a.xplane:
        path = a.xplane
    elif a.workload and a.seed is not None:
        path = str(run_kept(a.workload, a.seed, a.seconds, Path(a.out)))
    else:
        ap.error("give --xplane, or --workload and --seed")
    print(json.dumps({"xplane": path, **idle_by_span(path)}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
