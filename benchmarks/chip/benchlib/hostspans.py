"""The program's own spans on the profiler's clock, and the per-layer
numbers read from them.

`repro.core.trace.TraceRecorder` stamps `perf_counter() - origin`; the
profiler's host times count from its session's start. `AnchoredWindow`
reads `perf_counter` just before and just after it enters the
"bench:window" span, and again around its exit. `clock_map` then maps a
`perf_counter` reading linearly from the two anchors (each the midpoint
of its pair) onto the span's start and end in the trace: a second anchor
takes out the drift between the two clocks that one anchor leaves.

The readers take the events a recorder kept while it was switched on for
the window, and nothing else from the program:

- `msg_wait_p95_us`: enqueue to drain of the Submit and Done messages
  (`msg_enqueued` / `msg_drained`, paired by task and kind): queue
  residency;
- `manager_us_per_task`: summed `manager` spans over the tasks ended;
- `dispatch_us_per_task`: mean `start` -> `end` of a task body, the host
  dispatch of its jitted call;
- `idle_undispatched_pct`: share of the window in which the chip is
  idle, the main thread is inside "bench:taskwait", and no runtime
  thread is inside a task body;
- `step_host_ms`: the serving engine's `admit` + `dispatch` + `track`
  per step that dispatched.
"""
from __future__ import annotations

import shutil
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from . import tracered
from .common import Window, percentile

Span = Tuple[float, float]

# the recorder's event kinds and span names (`repro.core.trace`), spelt
# out so that reading a program without them finds nothing, not fails
EV_START, EV_END, EV_SPAN = "start", "end", "span"
EV_MSG_ENQ, EV_MSG_DRAIN = "msg_enqueued", "msg_drained"
MANAGER = "manager"
ADMIT, DISPATCH, TRACK = "admit", "dispatch", "track"
TASKWAIT_SPAN = tracered.SPAN_PREFIX + "taskwait"


class AnchoredWindow(Window):
    """A `Window` that also reads `perf_counter` around the entry and the
    exit of its span: `anchors` = ((before, after) entry, (before,
    after) exit). `trace` keeps the extracted profiler events."""

    def __init__(self, run):
        super().__init__(run)
        self.anchors: List[Span] = []
        self.trace: Optional[tracered.TraceEvents] = None

    def start(self) -> None:
        import jax
        d = self.run.trace_dir
        shutil.rmtree(d, ignore_errors=True)
        d.mkdir(parents=True, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(d), profiler_options=opts)
        self.span = jax.profiler.TraceAnnotation(tracered.WINDOW_SPAN)
        a = time.perf_counter()
        self.span.__enter__()
        self.t0 = time.perf_counter()
        self.anchors = [(a, self.t0)]

    def stop(self) -> None:
        import jax
        self.t1 = time.perf_counter()
        self.span.__exit__(None, None, None)
        self.anchors.append((self.t1, time.perf_counter()))
        jax.profiler.stop_trace()

    def reduce(self) -> Optional[tracered.Reduced]:
        if self.t1 is None:
            return None
        self.trace = tracered.extract(
            tracered.find_xplane(str(self.run.trace_dir)))
        shutil.rmtree(self.run.trace_dir, ignore_errors=True)
        return tracered.reduce(self.trace)


def clock_map(anchors: Sequence[Span],
              window: Span) -> Callable[[float], float]:
    """perf_counter reading -> profiler seconds, linear through the
    midpoints of the entry and exit anchors and the window's ends."""
    (a0, a1), (b0, b1) = anchors
    p0, p1 = (a0 + a1) / 2, (b0 + b1) / 2
    w0, w1 = window
    scale = (w1 - w0) / (p1 - p0)
    return lambda t: w0 + (t - p0) * scale


def bodies_on_profiler(events, origin: float,
                       to_profiler: Callable[[float], float]) -> List[Span]:
    """Task bodies (`start` -> `end`, paired by task) in profiler time."""
    return [(to_profiler(origin + s), to_profiler(origin + e))
            for s, e in _bodies(events)]


# ------------------------------------------------------------ readers
def msg_wait_p95_us(events) -> Optional[float]:
    """95th percentile of the queue residency of the messages both
    enqueued and drained."""
    enq: Dict[Tuple[int, str], float] = {}
    waits = []
    for e in events:
        if e.ev == EV_MSG_ENQ:
            enq[(e.wd_id, e.data[0])] = e.t
        elif e.ev == EV_MSG_DRAIN:
            t = enq.pop((e.wd_id, e.data[0]), None)
            if t is not None:
                waits.append(1e6 * (e.t - t))
    return percentile(waits, 95) if waits else None


def manager_us_per_task(events) -> Optional[float]:
    ended = sum(1 for e in events if e.ev == EV_END)
    sessions = [e.data[0] - e.t for e in events
                if e.ev == EV_SPAN and e.label == MANAGER]
    if not ended or not sessions:
        return None
    return 1e6 * sum(sessions) / ended


def dispatch_us_per_task(events) -> Optional[float]:
    spans = _bodies(events)
    if not spans:
        return None
    return 1e6 * sum(e - s for s, e in spans) / len(spans)


def idle_undispatched_pct(trace: tracered.TraceEvents,
                          bodies: Sequence[Span]) -> Optional[float]:
    """Share of the window, averaged over the chips traced, in which the
    chip runs nothing, the main thread waits in `taskwait`, and no
    runtime thread runs a task body."""
    lo, hi = tracered.window_of(trace)
    planes = sorted(set(trace.ops) | set(trace.modules))
    waits = _union([(s, e) for n, s, e in trace.spans if n == TASKWAIT_SPAN])
    if not planes or not waits or hi <= lo:
        return None
    free = _subtract(waits, _union(bodies))
    total = 0.0
    for plane in planes:
        ops = trace.ops.get(plane) or trace.modules.get(plane, [])
        idle = tracered.gaps(tracered.union(tracered.clip(ops, lo, hi)),
                             lo, hi)
        total += _length(_intersect(idle, free))
    return 100.0 * total / len(planes) / (hi - lo)


def step_host_ms(events) -> Optional[float]:
    """Mean over the engine steps that dispatched of their admit,
    dispatch and track spans."""
    host, steps, admit = 0.0, 0, 0.0
    for e in events:
        if e.ev != EV_SPAN:
            continue
        d = e.data[0] - e.t
        if e.label == ADMIT:
            admit = d
        elif e.label == DISPATCH:
            host += admit + d
            steps += 1
        elif e.label == TRACK:
            host += d
    return 1e3 * host / steps if steps else None


# ------------------------------------------------------- intervals
def _bodies(events) -> List[Span]:
    open_at: Dict[int, float] = {}
    out = []
    for e in events:
        if e.ev == EV_START:
            open_at[e.wd_id] = e.t
        elif e.ev == EV_END and e.wd_id in open_at:
            out.append((open_at.pop(e.wd_id), e.t))
    return out


def _union(spans: Sequence[Span]) -> List[Span]:
    return tracered.union([(None, s, e) for s, e in spans])


def _length(spans: Sequence[Span]) -> float:
    return sum(e - s for s, e in spans)


def _intersect(a: Sequence[Span], b: Sequence[Span]) -> List[Span]:
    """Both sorted and disjoint."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _subtract(a: Sequence[Span], b: Sequence[Span]) -> List[Span]:
    """a less b, both sorted and disjoint."""
    out = []
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out
