"""The program's recorder spans set on the profiler's clock, and the
readers of the runtime's and the engine's spans, on made-up event lists
with known answers."""
import time
from types import SimpleNamespace

import pytest

from benchpath import BENCH  # noqa: F401
from benchlib import hostspans as hs
from benchlib import tracered
from benchlib.common import span
from repro.core.trace import TraceEvent, TraceRecorder

DEV = "/device:TPU:0"


def ev(t, kind, wd_id=-1, slot=0, label="", data=None):
    return TraceEvent(t, kind, wd_id, slot, label, None, data)


def test_recorder_spans_land_inside_their_profiler_spans(tmp_path):
    """On the CPU's profiler: each recorder span stamped inside a
    "bench:" annotation maps, through the window's two anchors, to
    within 20 us of that annotation."""
    w = hs.AnchoredWindow(SimpleNamespace(trace_dir=tmp_path / "trace"))
    rec = TraceRecorder(1)
    w.start()
    for i in range(12):
        with span(f"probe{i}"):
            t0 = rec.clock()
            until = time.perf_counter() + 0.002
            while time.perf_counter() < until:
                pass
            rec.span("probe", 0, t0, i)
        time.sleep(0.02)
    w.stop()
    trace = tracered.extract(tracered.find_xplane(str(w.run.trace_dir)))
    to_prof = hs.clock_map(w.anchors, tracered.window_of(trace))
    probes = {n: (s, e) for n, s, e in trace.spans if "probe" in n}
    assert len(probes) == 12
    for e in rec.events():
        lo, hi = probes[f"bench:probe{e.data[1]}"]
        s = to_prof(rec.origin + e.t)
        end = to_prof(rec.origin + e.data[0])
        assert lo - 20e-6 <= s < end <= hi + 20e-6, (e.data[1], lo, s,
                                                      end, hi)


def test_clock_map_runs_through_both_anchor_midpoints():
    f = hs.clock_map([(10.0, 10.2), (20.0, 20.2)], (1.0, 11.5))
    assert f(10.1) == pytest.approx(1.0)
    assert f(20.1) == pytest.approx(11.5)
    assert f(15.1) == pytest.approx(6.25)


RUNTIME = [
    ev(0.0, "msg_enqueued", 1, 4, data=("submit", 4, 1)),
    ev(0.0, "msg_enqueued", 2, 4, data=("submit", 4, 1)),
    ev(1e-5, "span", -1, 0, "manager", (4e-5, 2)),
    ev(2e-5, "msg_drained", 1, 0, data=("submit", 4, 1)),
    ev(3e-5, "msg_drained", 2, 0, data=("submit", 4, 1)),
    ev(5e-5, "start", 1, 1), ev(6e-5, "start", 2, 2),
    ev(8e-5, "end", 1, 1),
    ev(8e-5, "msg_enqueued", 1, 1, data=("done", 1, 1)),
    ev(1.1e-4, "end", 2, 2),
    ev(1.2e-4, "span", -1, 3, "manager", (1.3e-4, 1)),
    ev(1.25e-4, "msg_drained", 1, 3, data=("done", 1, 1)),
    ev(1.3e-4, "msg_enqueued", 2, 2, data=("done", 2, 1)),   # undrained
]


@pytest.mark.parametrize("reader,want", [
    # waits 20, 30 and 45 us: numpy's linear 95th percentile
    (hs.msg_wait_p95_us, 30.0 + 0.9 * 15.0),
    # sessions of 30 and 10 us over two tasks ended
    (hs.manager_us_per_task, 20.0),
    # bodies of 30 and 50 us
    (hs.dispatch_us_per_task, 40.0),
])
def test_runtime_readers(reader, want):
    assert reader(RUNTIME) == pytest.approx(want)


@pytest.mark.parametrize("reader", [hs.msg_wait_p95_us,
                                    hs.manager_us_per_task,
                                    hs.dispatch_us_per_task,
                                    hs.step_host_ms])
def test_readers_find_nothing_in_a_program_without_spans(reader):
    """A recorder without spans (or none at all) reads as nothing."""
    assert reader([]) is None
    if reader is hs.manager_us_per_task:
        assert reader([e for e in RUNTIME if e.ev != "span"]) is None


def test_step_host_ms_counts_steps_that_dispatched():
    evs = [ev(0.000, "span", label="admit", data=(0.002, 1)),   # 2 ms
           ev(0.002, "span", label="dispatch", data=(0.003, None)),
           ev(0.003, "span", label="readback", data=(0.040, None)),
           ev(0.040, "span", label="track", data=(0.041, None)),
           ev(0.041, "span", label="admit", data=(0.042, 0)),   # idle
           ev(0.050, "span", label="admit", data=(0.051, 0)),   # 1 ms
           ev(0.051, "span", label="dispatch", data=(0.053, None)),
           ev(0.053, "span", label="readback", data=(0.090, None)),
           ev(0.090, "span", label="track", data=(0.093, None))]
    # (2 + 1 + 1) and (1 + 2 + 3) ms: the readbacks and the admission of
    # the step that dispatched nothing are left out
    assert hs.step_host_ms(evs) == pytest.approx(5.0)


def test_idle_undispatched_pct():
    trace = tracered.TraceEvents(
        ops={DEV: [("gemm", 1.0, 2.0), ("gemm", 6.0, 7.0)]},
        modules={},
        spans=[("bench:window", 0.0, 10.0), ("bench:alloc", 0.0, 0.5),
               ("bench:taskwait", 0.5, 8.0)])
    bodies = [(0.6, 1.2), (3.0, 4.0), (3.5, 4.5), (9.0, 9.5)]
    # idle in taskwait: [0.5, 1) + [2, 6) + [7, 8) = 5.5 s; bodies take
    # [0.6, 1) and [3, 4.5) of it: 3.6 s of 10 left
    assert hs.idle_undispatched_pct(trace, bodies) == pytest.approx(36.0)
    red = tracered.reduce(trace)
    assert hs.idle_undispatched_pct(trace, bodies) <= 100 * red.idle_share
    no_wait = tracered.TraceEvents(ops=trace.ops, modules={},
                                   spans=trace.spans[:2])
    assert hs.idle_undispatched_pct(no_wait, bodies) is None


def test_bodies_on_profiler_pair_by_task():
    to_prof = hs.clock_map([(100.0, 100.0), (110.0, 110.0)], (0.0, 20.0))
    got = hs.bodies_on_profiler(RUNTIME, 100.0, to_prof)
    assert got == [pytest.approx((1e-4, 1.6e-4)),
                   pytest.approx((1.2e-4, 2.2e-4))]
