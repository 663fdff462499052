"""From a profiler trace to device busy time, per-op time and idle gaps.

`extract` reads the `.xplane.pb` the JAX profiler writes into plain
lists of (name, start_s, end_s): the device's ops and its programs
("XLA Ops" and "XLA Modules" lines of each "/device:" plane), and the
benchmark's own host spans (`jax.profiler.TraceAnnotation` names that
start with "bench:"). `reduce` works on those lists only, so it is
checked without a chip on a small recorded trace.

The window is the host span "bench:window". Busy time is the union of
the device's op intervals inside it, averaged over the chips traced; an
idle gap is a stretch of the window in which no op runs; each part of it
is put down to the innermost host span open then on the main thread.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Interval = Tuple[str, float, float]
WINDOW_SPAN = "bench:window"
SPAN_PREFIX = "bench:"
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


@dataclass
class TraceEvents:
    # per device plane: ops and programs, each (name, start_s, end_s)
    ops: Dict[str, List[Interval]] = field(default_factory=dict)
    modules: Dict[str, List[Interval]] = field(default_factory=dict)
    # host spans of the thread that opened the window
    spans: List[Interval] = field(default_factory=list)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def short_name(name: str) -> str:
    """An op's name without its HLO text: "%fusion.3 = f32[..] ..." ->
    "fusion.3"; a custom call keeps its target in front of its name:
    "tpu_custom_call:custom-call.7" (a Pallas kernel on the TPU)."""
    short = name.split(" = ", 1)[0].lstrip("%")
    m = _TARGET.search(name)
    return f"{m.group(1)}:{short}" if m else short


def extract(path: str) -> TraceEvents:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ev = TraceEvents()
    host_lines: Dict[str, List[Interval]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dest = ev.ops.setdefault(plane.name, [])
                elif line.name == "XLA Modules":
                    dest = ev.modules.setdefault(plane.name, [])
                else:
                    continue
                for e in line.events:
                    s = e.start_ns * 1e-9
                    dest.append((short_name(e.name), s,
                                 s + e.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = [(e.name, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                         for e in line.events
                         if e.name.startswith(SPAN_PREFIX)]
                if spans:
                    host_lines[f"{plane.name}/{line.name}"] = spans
    for spans in host_lines.values():
        if any(n == WINDOW_SPAN for n, _, _ in spans):
            ev.spans = sorted(spans, key=lambda x: x[1])
            break
    return ev


# ------------------------------------------------------------- reduction
def window_of(ev: TraceEvents) -> Tuple[float, float]:
    for name, s, e in ev.spans:
        if name == WINDOW_SPAN:
            return s, e
    raise ValueError("the trace holds no bench:window span")


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in intervals
            if e > lo and s < hi]


def union(intervals: List[Interval]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy: List[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def span_at(spans: List[Interval], t: float) -> str:
    """Innermost host span (other than the window) open at time t."""
    best: Optional[Interval] = None
    for name, s, e in spans:
        if name != WINDOW_SPAN and s <= t < e:
            if best is None or e - s < best[2] - best[1]:
                best = (name, s, e)
    return best[0][len(SPAN_PREFIX):] if best else "other"


def attribute(gap: Tuple[float, float], spans: List[Interval],
              into: Dict[str, float], weight: float = 1.0) -> None:
    """Split an idle gap by the innermost host span open in each part."""
    lo, hi = gap
    inside = [x for x in spans if x[1] < hi and x[2] > lo]
    cuts = sorted({lo, hi} | {t for _, s, e in inside for t in (s, e)
                              if lo < t < hi})
    for a, b in zip(cuts, cuts[1:]):
        into[span_at(inside, (a + b) / 2)] += (b - a) * weight


def per_name(intervals: List[Interval]) -> Dict[str, Tuple[int, float]]:
    acc: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    for n, s, e in intervals:
        acc[n][0] += 1
        acc[n][1] += e - s
    return {n: (int(c), t) for n, (c, t) in acc.items()}


@dataclass
class Reduced:
    window_s: float
    busy_s: float                         # averaged over the chips traced
    op_time: Dict[str, Tuple[int, float]]     # summed over chips
    module_time: Dict[str, Tuple[int, float]]
    idle_by_span: Dict[str, float]        # averaged over the chips traced
    chips: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_time.items(), key=lambda kv: -kv[1][1])[:top]
        idle = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, t] for n, (_, t) in ops],
                "idle_gaps": [[n, t] for n, t in idle]}


def reduce(ev: TraceEvents) -> Reduced:
    lo, hi = window_of(ev)
    planes = sorted(set(ev.ops) | set(ev.modules))
    if not planes:
        raise ValueError("the trace holds no device plane")
    busy_total = 0.0
    idle: Dict[str, float] = defaultdict(float)
    all_ops: List[Interval] = []
    all_mods: List[Interval] = []
    for plane in planes:
        ops = clip(ev.ops.get(plane) or ev.modules.get(plane, []), lo, hi)
        mods = clip(ev.modules.get(plane, []), lo, hi)
        all_ops += ops
        all_mods += mods
        busy = union(ops)
        busy_total += sum(e - s for s, e in busy)
        for gap in gaps(busy, lo, hi):
            attribute(gap, ev.spans, idle, 1.0 / len(planes))
    return Reduced(window_s=hi - lo, busy_s=busy_total / len(planes),
                   op_time=per_name(all_ops), module_time=per_name(all_mods),
                   idle_by_span=dict(idle), chips=len(planes))


def module_stats(red: Reduced, fragment: str) -> Tuple[int, float]:
    """(count, seconds) of the device programs whose name holds fragment."""
    n, t = 0, 0.0
    for name, (c, s) in red.module_time.items():
        if fragment in name:
            n += c
            t += s
    return n, t


def op_stats(red: Reduced, fragment: str) -> Tuple[int, float]:
    n, t = 0, 0.0
    for name, (c, s) in red.op_time.items():
        if fragment in name:
            n += c
            t += s
    return n, t

