"""Reduce a profiler trace (`.xplane.pb`) to the benchmark's device numbers.

Read with `jax.profiler.ProfileData`, nothing else:

  * device planes are the chips' planes (`/device:TPU:<n>`); the per-op
    line is "XLA Ops", and busy time is the union of its events'
    intervals (other lines, such as "XLA Modules" or "Steps", repeat the
    same time at a coarser grain and are used only when no "XLA Ops" line
    exists);
  * the window is the benchmark's own host annotation `<prefix>window`,
    so busy and idle are counted over exactly the measured rounds;
  * each idle gap is labelled `<phase>/<span>`: the host annotation of
    the benchmark (`<prefix>...`, one per phase of a round) that covers
    the gap's midpoint ("other" where none does), then the innermost
    program span (`repro:...`, from `repro.obs.span`) open on the host
    there ("-" where none was): what the host was doing while the device
    sat idle.  Idle seconds are summed by phase (`idle_by_label`) and by
    both (`idle_by_span`);
  * busy time is counted per chip (`busy_by_chip`, by the device plane's
    name) and averaged over the chips (`busy_s`);
  * a TPU op event is named by its HLO instruction text
    ("%rss_scan_agg_grouped.1 = s32[...] custom-call(...), ..."); op time
    is summed by `op_label`, the instruction's name without its numeric
    suffix and its opcode ("rss_scan_agg_grouped:custom-call"), so one
    label covers the same op in programs of every shape.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "repro:"                 # the program's spans (`repro.obs.span`)
_OPCODE = re.compile(r"\s([A-Za-z][\w\-]*)\(")
_CHIP = re.compile(r"/device:(TPU|GPU):\d+$")


def op_label(name: str) -> str:
    """"<instruction name without .N>:<opcode>" of an HLO op event; any
    other event name is kept as it is."""
    head, sep, rest = name.partition(" = ")
    if not sep or not head.startswith("%"):
        return name
    base = re.sub(r"\.\d+$", "", head[1:])
    m = _OPCODE.search(rest)
    return f"{base}:{m.group(1)}" if m else base


@dataclass
class TraceSummary:
    window_s: float                      # the annotated window's length
    busy_s: float                        # device busy, averaged over chips
    n_devices: int
    op_seconds: dict = field(default_factory=dict)    # op_label -> s, all chips
    idle_gaps: list = field(default_factory=list)     # [(label, s)] longest
    idle_by_label: dict = field(default_factory=dict)  # phase -> idle s
    idle_by_span: dict = field(default_factory=dict)   # phase/span -> idle s
    busy_by_chip: dict = field(default_factory=dict)   # plane -> busy s

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s else 0.0

    def seconds_where(self, pred) -> float:
        """Summed op time of the ops whose label satisfies `pred` (over
        all chips)."""
        return sum(s for op, s in self.op_seconds.items() if pred(op))

    def top_ops(self, n: int = 10) -> list:
        return sorted(([k, v] for k, v in self.op_seconds.items()),
                      key=lambda kv: -kv[1])[:n]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


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


def _device_lines(plane):
    lines = list(plane.lines)
    ops = [ln for ln in lines if ln.name == OPS_LINE]
    return ops or lines


def reduce_trace(path: str, *, prefix: str = "bench:",
                 n_gaps: int = 10) -> TraceSummary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans = []                                    # host annotations
    program = []                                  # program spans
    devices = []                          # per chip: (plane, [(s, e, name)])
    for plane in pd.planes:
        if _CHIP.match(plane.name):
            evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for ln in _device_lines(plane) for e in ln.events]
            if evs:
                devices.append((plane.name, evs))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    ev = (e.start_ns, e.start_ns + e.duration_ns)
                    if e.name.startswith(prefix):
                        spans.append((*ev, e.name[len(prefix):]))
                    elif e.name.startswith(SPAN_PREFIX):
                        program.append((*ev, e.name[len(SPAN_PREFIX):]))
    win = [(s, e) for s, e, n in spans if n == "window"]
    if not win:
        raise ValueError(f"trace has no {prefix}window annotation")
    w0, w1 = win[0]
    # the benchmark's phase annotations follow one another inside the
    # window without overlapping, so the one covering t is the last that
    # starts at or before t
    inner = sorted(sp for sp in spans if sp[2] != "window")
    starts = [sp[0] for sp in inner]

    def label(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        return inner[i][2] if i >= 0 and t < inner[i][1] else "other"

    op_s: dict = {}
    busy_total = 0.0
    by_chip: dict = {}
    gaps: list = []
    for plane, evs in devices:
        clipped = [(max(s, w0), min(e, w1)) for s, e, _ in evs
                   if e > w0 and s < w1]
        for s, e, name in evs:
            if e > w0 and s < w1:
                op = op_label(name)
                op_s[op] = op_s.get(op, 0.0) + \
                    (min(e, w1) - max(s, w0)) * 1e-9
        merged = _union(clipped)
        busy = sum(e - s for s, e in merged)
        busy_total += busy
        by_chip[plane] = busy * 1e-9
        t = w0
        for s, e in merged + [[w1, w1]]:
            if s > t:
                gaps.append((s - t, t, s))
            t = max(t, e)
    n_dev = len(devices)
    # the program span open at each gap's midpoint, taken in time order
    order = sorted(range(len(gaps)), key=lambda i: gaps[i][1] + gaps[i][2])
    span_at = dict(zip(order, _innermost(
        program, [(gaps[i][1] + gaps[i][2]) / 2 for i in order])))
    by_label: dict = {}
    by_span: dict = {}
    labelled = []
    for i, (dur, s, e) in enumerate(gaps):
        lab = label((s + e) / 2)
        by_label[lab] = by_label.get(lab, 0.0) + dur * 1e-9
        lab = f"{lab}/{span_at[i] or '-'}"
        by_span[lab] = by_span.get(lab, 0.0) + dur * 1e-9
        labelled.append((dur, lab))
    labelled.sort(key=lambda g: -g[0])
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy_total * 1e-9 / max(n_dev, 1),
        n_devices=n_dev,
        op_seconds=op_s,
        idle_gaps=[[lab, dur * 1e-9] for dur, lab in labelled[:n_gaps]],
        idle_by_label=by_label,
        idle_by_span=by_span,
        busy_by_chip=by_chip)
