#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in `BENCHMARK.json`; its configuration
and traffic mix are found by name under `bench/configs/` and
`bench/mixes/`, and each per-layer metric's reader under `bench/layers/`.
One run is one process: it loads the deployment, warms it up with the
mix's own traffic from the seed, compiles every kernel shape that traffic
can meet on each of the cell's chips (`jax.local_devices()[:chips]`), runs
the traffic again for two refresh or ship cadences so that the snapshots
catch up on that pause, measures for `--seconds`, checks a sample of the
window's served results against the plain reference (`bench/reference.py`),
and prints one JSON object as the last line of stdout.  With `--trace 0`
the metrics are the cell's end-to-end metrics; with `--trace 1` the window
runs under the JAX profiler and the metrics are the per-layer ones, with
the device's busy time (also per chip, `busy_by_chip`) and a breakdown.
`device` gives the peak memory of the fullest chip and of each
(`memory_peak_by_chip`).
The numbers compared for `correct` are printed, each beside its limit, as
the last lines of stderr and under `checks` in the result line.

Without a TPU, with fewer chips than the cell asks for, without the
program (`src/repro`) beside it, when the kernel warm-up cannot replay a
recorded kernel call, or when a program is built inside the measured
window, it exits 1 and prints no result.  The
persistent compile cache is `.jax_cache/` in the checkout.

`--control` runs a correctness control, and `correct` has to come out
false: with `latest` the reference answers in the program's place from
every acknowledged commit (its snapshot guarantee broken); with
`lost_updates` the engine commits with first-committer-wins and
certification off (`Run.plant_lost_updates`).  Both may be given.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
CHECK_SAMPLE = 400         # served results compared per run


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("latest", "lost_updates"),
                    action="append", default=[])
    return ap.parse_args(argv)


def find_cell(name: str) -> tuple[dict, dict]:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return bench, cell
    raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")


def configure_compile_cache() -> str:
    """JAX's persistent compile cache at a fixed path inside the checkout
    (the path is part of the cache's key), whatever the environment
    names: two checkouts on one machine share nothing."""
    import jax
    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int, *, require_tpu: bool = True) -> dict | None:
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        print(f"run.py: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return None
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peaks() -> dict:
    """Each local device's peak bytes in use, by its trace plane's name."""
    import jax
    return {f"/device:{d.platform.upper()}:{d.id}":
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in jax.local_devices()}


def layer_metrics(bench: dict, cell: dict, li) -> dict:
    """Each per-layer metric of the cell, from its reader; a reader that
    finds nothing returns None and the metric is left out."""
    from bench.harness import load_reader
    e2e = {m["name"] for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])}
    out = {}
    for m in bench["per_layer"]:
        cells = m.get("workloads")
        if (cell["name"] not in cells) if cells is not None \
                else (m["moves"] not in e2e):
            continue
        value = load_reader(m["name"])(li)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(args, *, require_tpu: bool = True, t_start: float = T_START,
             out=sys.stdout, err=sys.stderr) -> int:
    bench, cell = find_cell(args.workload)
    if importlib.util.find_spec("repro") is None:
        print("run.py: the program under test (src/repro) is not in this "
              "checkout", file=err)
        return 1
    device = device_info(cell["chips"], require_tpu=require_tpu)
    if device is None:
        return 1
    import jax
    print(f"compile cache: {configure_compile_cache()}", file=err)
    from bench import cost
    from bench.harness import (CompileClock, LayerInput, Run, checks_pass,
                               load_config)
    from bench.kernel_warmup import KernelWarmup, WarmupError, chip_name
    from bench.trace_reduce import find_xplane, reduce_trace

    peaks = cost.peaks(device["kind"]) if require_tpu else {}
    cfg = load_config(cell["config"])
    clock = CompileClock()
    marks = [("start", t_start), ("jax", time.perf_counter())]
    chips = jax.local_devices()[:cell["chips"]]
    try:
        warm = KernelWarmup(chips)
    except WarmupError as exc:
        print(f"run.py: kernel warm-up failed: {exc}", file=err)
        return 1
    run = Run(cfg, cell["traffic"], args.seed, annotate=bool(args.trace))
    marks.append(("build", time.perf_counter()))
    run.load()
    if "lost_updates" in args.control:
        run.plant_lost_updates()
    marks.append(("load", time.perf_counter()))
    run.warm_up(run.mix.warmup_rounds)
    marks.append(("warm_up", time.perf_counter()))
    try:
        warm.exercise(run.warm_plans(), slots=cfg["page"]["slots"],
                      page_elems=cfg["page"]["elems"])
        n_warm = warm.replay()
    except WarmupError as exc:
        warm.stop()
        print(f"run.py: kernel warm-up failed: {exc}", file=err)
        return 1
    per_chip = ", ".join(f"{n} on {c}" for c, n in warm.per_chip.items())
    under = ", ".join(chip_name(c) if c else "none" for c in warm.served_under)
    print(f"kernel warm-up: {n_warm} calls ({per_chip}; scratch serves "
          f"under default device {under}) in "
          f"{time.perf_counter() - marks[-1][1]:.3f} s, member arrays up to "
          f"{warm.members_to()} long (the traffic passed up to "
          f"{warm.longest})", file=err)
    marks.append((f"{n_warm} kernel shapes", time.perf_counter()))
    run.warm_up(run.settle_rounds())
    marks.append(("settle", time.perf_counter()))
    setup_s = marks[-1][1] - t_start
    steps = ", ".join(f"{name} {t - t0:.3f} s" for (_, t0), (name, t)
                      in zip(marks, marks[1:]))
    print(f"set-up: {setup_s:.3f} s ({steps}); {clock}", file=err)
    clock.reset()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace \
        else None
    try:
        if trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host annotations, no Python
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            window = run.measure(args.seconds)
        finally:
            if trace_dir:
                jax.profiler.stop_trace()
        clock.close()
        print(f"window: {window.seconds:.3f} s (trace {args.trace}), "
              f"{run.round} rounds in all; {window.oltp_aborts} "
              f"certification aborts retried; inside it {clock}", file=err)
        if clock.compiles or clock.cache_loads:
            print(f"run.py: programs were built inside the measured window "
                  f"({', '.join(clock.names) or 'loaded from the cache'}); "
                  f"the kernel warm-up missed their shapes", file=err)
            return 1
        members = [len(snap[1]) for _s, snap, _r, _t in window.served]
        print(f"served snapshots: {len(members)} plans, members above the "
              f"floor {min(members, default=0)}..{max(members, default=0)} "
              f"({len(set(members))} distinct counts)", file=err)
        peaks_by_chip = memory_peaks()
        device["memory_peak_bytes"] = max(peaks_by_chip.values())
        device["memory_peak_by_chip"] = peaks_by_chip
        summary = reduce_trace(find_xplane(trace_dir)) if trace_dir else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    run.release_program()
    if args.trace:
        li = LayerInput.of(run, summary, peaks)
        metrics = layer_metrics(bench, cell, li)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        device["busy_by_chip"] = summary.busy_by_chip
        print(f"trace: {summary.n_devices} device(s), busy "
              f"{summary.busy_s:.6f} s of {summary.window_s:.6f} s "
              f"(by chip {summary.busy_by_chip}); ops "
              f"{summary.top_ops(40)}; idle by phase "
              f"{summary.idle_by_label}; by phase and span "
              f"{summary.idle_by_span}", file=err)
    else:
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in run.end_to_end(setup_s).items()
                   if k in units}
    t_check = time.perf_counter()
    checks = run.check(sample=CHECK_SAMPLE, control=args.control)
    print(f"check: {time.perf_counter() - t_check:.3f} s against the "
          f"reference", file=err)
    correct = checks_pass(checks)
    w = run.window
    # a terminal retries its certification aborts until the transaction
    # commits, so what can fail is an analytic query: aborted or made to wait
    result = {"correct": correct,
              "attempted": w.oltp_attempts + w.queries_begun,
              "failed": w.olap_aborts + w.olap_waits,
              "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        rel = "at least" if k == "results_checked" else "at most"
        print(f"check {k}: {v} ({rel} {lim})", file=err)
    err.flush()
    print(json.dumps(result), file=out)
    return 0


def main(argv=None) -> int:
    return run_cell(parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
