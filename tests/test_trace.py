"""Per-task event tracing (core.trace): the recorder's ring semantics,
the shared event schema on BOTH drivers (threaded lifecycle + monotone
merged timestamps; sim-vs-threaded per-task agreement on an oracle
graph), the three detrimental-pattern detectors with positive AND
negative oracles (including the replay-window false-positive fix), the
tuner feedback hook, the stats satellites (worker steals, load-cap
skips, per-scope steal rollups), and the Perfetto/Chrome exporter."""
import json
import time
from collections import Counter

import pytest

from repro.core import (DynamicTuner, RuntimeSimulator, SimTaskSpec,
                        TaskRuntime, TunerConfig)
from repro.core.sched.placement import ShardAffinePlacement
from repro.core.taskgraph_apps import sim_matmul_specs
from repro.core.trace import (AFFINITY_MISS, COUNT_EMPTY_POLL,
                              EV_ADMIT_DEFER, EV_CREATED, EV_DELEGATE,
                              EV_DEPS, EV_END, EV_MSG_DRAIN, EV_MSG_ENQ,
                              EV_QUIESCE, EV_READY, EV_SPAN, EV_START,
                              EV_STEAL, INVERSION, NULL_TRACER, SPAN_ADMIT,
                              SPAN_DISPATCH, SPAN_MANAGER, SPAN_PREFILL,
                              SPAN_READBACK, SPAN_TRACK, STARVATION,
                              TASK_LIFECYCLE,
                              Finding, TraceEvent, TraceRecorder,
                              detect_affinity_misses, detect_all,
                              detect_priority_inversion, detect_starvation,
                              load_trace, replay_windows, save_trace,
                              span_end)
from repro.core.wd import DepMode, WorkDescriptor

ALL_MODES = ("sync", "dast", "ddast", "sharded")

IN, OUT, INOUT = DepMode.IN, DepMode.OUT, DepMode.INOUT


def _spin(ms: float = 0.0002):
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < ms:
        pass


def _chain_fanout_specs(n_chains: int = 4, depth: int = 4):
    """Small oracle graph: a root, then per-chain INOUT chains — every
    task has dependences, every label is unique."""
    specs = [SimTaskSpec(dur=40, deps=[(("root",), OUT)], label="root")]
    for c in range(n_chains):
        specs.append(SimTaskSpec(
            dur=25, deps=[(("root",), IN), (("ch", c), OUT)],
            label=f"head{c}"))
        for j in range(depth):
            specs.append(SimTaskSpec(
                dur=25, deps=[(("ch", c), INOUT)], label=f"c{c}_{j}"))
    return specs


def _mk(t, ev, wd_id=-1, slot=-1, label="", scope=None, data=None):
    return TraceEvent(t, ev, wd_id, slot, label, scope, data)


# ------------------------------------------------------------ recorder
def test_null_tracer_is_shared_and_silent():
    with TaskRuntime(num_workers=2, mode="ddast") as rt:
        rt.task(_spin)
        rt.taskwait()
        assert rt.tracer is NULL_TRACER      # one shared stub, no rings
    assert rt.stats.events == []
    assert rt.stats.trace_dropped == 0
    assert NULL_TRACER.total_appended == 0
    assert NULL_TRACER.events() == []


def test_recorder_ring_drops_oldest_per_slot():
    clock = iter(range(100))
    rec = TraceRecorder(2, clock=lambda: next(clock), capacity=4)
    wd = WorkDescriptor(func=None, label="x")
    for _ in range(7):
        rec.task_event(EV_READY, wd, 0)
    assert rec.dropped == 3
    kept = [e.t for e in rec.events()]
    assert kept == [3, 4, 5, 6]              # oldest evicted first


def test_recorder_overflow_slot_routing():
    rec = TraceRecorder(2, clock=lambda: 0.0)
    wd = WorkDescriptor(func=None, label="x")
    rec.task_event(EV_READY, wd, -1)         # unattributed producer
    rec.task_event(EV_READY, wd, 99)         # out of range
    rec.mgr_event(EV_MSG_ENQ, -1, data=("submit", 0, 1))
    assert len(rec._rings[2]) == 3           # all in the overflow ring
    assert len(rec.events()) == 3


def test_recorder_save_load_round_trip(tmp_path):
    rec = TraceRecorder(2, clock=lambda: 1.5, time_unit="us")
    wd = WorkDescriptor(func=None, label="t0")
    rec.task_event(EV_READY, wd, 0, data=("band", 3))
    rec.quiesce({"scope": None, "replay_iterations": 2})
    p = tmp_path / "run.trace"
    rec.save(str(p))
    events, meta = load_trace(str(p))
    assert meta["time_unit"] == "us" and meta["num_slots"] == 2
    assert events[0].ev == EV_READY and events[0].label == "t0"
    assert list(events[0].data) == ["band", 3]   # tuples -> lists
    assert events[1].ev == EV_QUIESCE
    assert events[1].data["replay_iterations"] == 2


def test_save_trace_helper_for_results(tmp_path):
    res = RuntimeSimulator(4, "ddast", trace=True).run(
        _chain_fanout_specs())
    p = tmp_path / "sim.trace"
    save_trace(str(p), res.events, time_unit="us")
    events, meta = load_trace(str(p))
    assert len(events) == len(res.events)
    assert meta["time_unit"] == "us"


# ----------------------------------------------- threaded trace=True
@pytest.mark.parametrize("mode", ALL_MODES)
def test_threaded_lifecycle_and_monotone_timestamps(mode):
    with TaskRuntime(num_workers=4, mode=mode, trace=True) as rt:
        for i in range(24):
            rt.task(_spin, deps=[(("r", i % 4), "inout")],
                    label=f"t{i}")
        rt.taskwait()
    events = rt.stats.events
    assert events and rt.stats.trace_dropped == 0
    ts = [e.t for e in events]
    assert ts == sorted(ts)                  # merged sort is by time
    assert all(t >= 0.0 for t in ts)         # relative to run start
    per = {}
    starts, ends = {}, {}
    for e in events:
        if e.wd_id < 0:
            continue
        if e.ev in TASK_LIFECYCLE:
            per.setdefault(e.label, Counter())[e.ev] += 1
        if e.ev == EV_START:
            starts[e.wd_id] = e.slot
        elif e.ev == EV_END:
            ends[e.wd_id] = e.slot
    for i in range(24):
        c = per[f"t{i}"]
        assert c[EV_CREATED] == c[EV_READY] == 1
        assert c[EV_START] == c[EV_END] == 1
    # a body runs start-to-end on one slot
    assert starts == ends
    # quiesce boundary stamped at the root taskwait
    assert any(e.ev == EV_QUIESCE for e in events)


def test_threaded_scope_tagging():
    with TaskRuntime(num_workers=2, mode="sync", trace=True,
                     num_clients=1) as rt:
        sc = rt.open_scope("tenant")
        for i in range(6):
            sc.task(_spin, deps=[(("A",), "inout")], label=f"s{i}")
        sc.taskwait()
        sid = sc.scope_id
        sc.close()
    tagged = [e for e in rt.stats.events
              if e.ev in TASK_LIFECYCLE and e.label.startswith("s")]
    assert tagged
    assert all(e.scope == sid for e in tagged)


# ------------------------------------- sim vs threaded schema agreement
@pytest.mark.parametrize("mode", ("ddast", "sharded"))
def test_sim_threaded_event_schema_agreement(mode):
    """Both drivers emit the same per-task event-kind multiset for the
    same logical graph (deps_resolved is per shard portion in sharded
    mode — on both drivers, since they share the router), and both
    attribute start/end of a body to one slot."""
    specs = _chain_fanout_specs()
    sim_res = RuntimeSimulator(4, mode, trace=True).run(specs)

    with TaskRuntime(num_workers=4, mode=mode, trace=True) as rt:
        for s in specs:
            rt.task(_spin, deps=[(r, m) for r, m in s.deps],
                    label=s.label)
        rt.taskwait()

    def per_label(events):
        out = {}
        for e in events:
            if e.wd_id >= 0 and e.ev in TASK_LIFECYCLE:
                out.setdefault(e.label, Counter())[e.ev] += 1
        return out

    sim_kinds = per_label(sim_res.events)
    thr_kinds = per_label(rt.stats.events)
    assert set(sim_kinds) == set(thr_kinds) == {s.label for s in specs}
    for label in sim_kinds:
        assert sim_kinds[label] == thr_kinds[label], label

    def start_end_slots(events):
        s, e_ = {}, {}
        for e in events:
            if e.ev == EV_START:
                s[e.wd_id] = e.slot
            elif e.ev == EV_END and e.wd_id in s:
                e_[e.wd_id] = e.slot
        return s, e_

    for evs in (sim_res.events, rt.stats.events):
        starts, ends = start_end_slots(evs)
        assert starts == ends


def test_sim_early_visibility_does_not_confuse_detectors():
    """The simulator's causality approximation can stamp a start with
    an earlier virtual time than the task's created/ready (a core
    running locally ahead published it 'into the past'). Detectors
    pair by wd_id, so a clean run stays clean."""
    specs = [SimTaskSpec(dur=50, deps=[(("a", 0), OUT)], label="w0")]
    for i in range(6):
        specs.append(SimTaskSpec(
            dur=30, deps=[(("a", 0), IN), ((i, 1), OUT)], label=f"r{i}"))
    res = RuntimeSimulator(4, "sync", trace=True).run(specs)
    by_label = {}
    for e in res.events:
        if e.label == "w0" and e.ev in (EV_CREATED, EV_START):
            by_label[e.ev] = e.t
    # the quirk this test is about: w0 starts "before" it is created
    assert by_label[EV_START] < by_label[EV_CREATED]
    assert detect_all(res.events) == []


# ------------------------------------------------- detectors: oracles
def _workers_present(t0=0.0):
    """Make workers 0 and 1 known to the sweep (busy maps populate at
    the first start), both idle again by t0."""
    w = WorkDescriptor(func=None, label="warm")
    return [
        _mk(t0 + 0.0, EV_START, wd_id=900, slot=0, label="warm"),
        _mk(t0 + 0.1, EV_END, wd_id=900, slot=0, label="warm"),
        _mk(t0 + 0.0, EV_START, wd_id=901, slot=1, label="warm"),
        _mk(t0 + 0.1, EV_END, wd_id=901, slot=1, label="warm"),
    ] if w else []


def test_starvation_positive_deep_deque():
    evs = _workers_present()
    # slot 1's deque piles up while worker 0 sits idle the whole span
    for i in range(5):
        evs.append(_mk(1.0 + i * 0.01, EV_READY, wd_id=i, slot=1,
                       label=f"t{i}"))
    evs.append(_mk(100.0, EV_END, wd_id=901, slot=1))   # span closer
    found = detect_starvation(evs)
    assert len(found) == 1
    f = found[0]
    assert f.kind == STARVATION and f.slot == 1
    assert not f.detail["backlog_only"]
    assert 0 in f.detail["idle_slots"]


def test_starvation_positive_stalled_backlog():
    evs = _workers_present()
    evs.append(_mk(1.0, EV_MSG_ENQ, data=("submit_batch", 0, 10)))
    evs.append(_mk(100.0, EV_MSG_DRAIN, data=("submit_batch", 0, 10)))
    found = detect_starvation(evs)
    assert len(found) == 1
    assert found[0].detail["backlog_only"]


def test_starvation_negative_draining_backlog_is_pipelining():
    """Deep mailboxes behind an ACTIVELY draining manager never flag:
    each drain closes the candidate span before it reaches min_dur."""
    evs = _workers_present()
    # prime a standing backlog well above backlog_min...
    evs.append(_mk(0.5, EV_MSG_ENQ, data=("submit_batch", 0, 20)))
    t = 1.0
    for _ in range(120):                    # ...then steady turnover
        evs.append(_mk(t, EV_MSG_ENQ, data=("submit", 0, 1)))
        evs.append(_mk(t + 0.25, EV_MSG_DRAIN, data=("submit", 0, 1)))
        t += 0.5
    assert detect_starvation(evs) == []


def test_starvation_negative_clean_sim_runs():
    for mode in ALL_MODES:
        res = RuntimeSimulator(16, mode, trace=True).run(
            sim_matmul_specs(8, dur_us=200), iterations=2)
        assert detect_starvation(res.events) == [], mode


def test_replay_window_suppresses_backlog_signal():
    """Replayed iterations are manager-silent by design: a window whose
    closing quiesce shows replay_iterations advanced must not flag
    backlog starvation (the detectors' replay false-positive fix)."""
    def timeline(iters_at_end):
        evs = _workers_present()
        evs.append(_mk(0.5, EV_QUIESCE,
                       data={"scope": None, "replay_iterations": 0}))
        # stale backlog + idle workers across (0.5, 100)
        evs.append(_mk(1.0, EV_MSG_ENQ, data=("submit_batch", 0, 10)))
        evs.append(_mk(100.0, EV_QUIESCE,
                       data={"scope": None,
                             "replay_iterations": iters_at_end}))
        return evs

    assert replay_windows(timeline(1)) == [(0.5, 100.0)]
    assert detect_starvation(timeline(1)) == []          # suppressed
    flagged = detect_starvation(timeline(0))             # live window
    assert len(flagged) == 1 and flagged[0].detail["backlog_only"]


def test_inversion_positive_and_negative():
    evs = []
    # a band-7 task ready early, never started...
    evs.append(_mk(0.0, EV_READY, wd_id=1, slot=0, label="hi",
                   data=("band", 7)))
    # ...while three band-0 tasks ready later all start before it
    for i in range(3):
        evs.append(_mk(0.5, EV_READY, wd_id=10 + i, slot=1,
                       label=f"lo{i}", data=("band", 0)))
        evs.append(_mk(1.0 + i, EV_START, wd_id=10 + i, slot=1,
                       label=f"lo{i}"))
    found = detect_priority_inversion(evs)
    assert len(found) == 1
    assert found[0].kind == INVERSION and found[0].count == 3
    # below min_count: scheduling jitter, not a pathology
    assert detect_priority_inversion(evs, min_count=4) == []
    # no bands published (live placement): detector stays silent
    res = RuntimeSimulator(8, "ddast", trace=True).run(
        sim_matmul_specs(6, dur_us=150))
    assert detect_priority_inversion(res.events) == []


def test_inversion_negative_critical_path_replay():
    """The banded lane drains highest band first, so a critical-path
    replay run is inversion-free by construction."""
    res = RuntimeSimulator(8, "ddast", trace=True, replay=True,
                           placement="critical_path").run(
        sim_matmul_specs(6, dur_us=150), iterations=3)
    assert any(e.ev == EV_READY and isinstance(e.data, tuple)
               and e.data[0] == "band" for e in res.events)
    assert detect_priority_inversion(res.events) == []


def test_affinity_positive_and_negative():
    evs = []
    for i in range(4):
        evs.append(_mk(1.0 + i, EV_READY, wd_id=i, slot=1,
                       label=f"a{i}", data="affine"))
        evs.append(_mk(2.0 + i, EV_STEAL, wd_id=i, slot=2,
                       label=f"a{i}", data=1))
        evs.append(_mk(2.1 + i, EV_START, wd_id=i, slot=2,
                       label=f"a{i}"))
    found = detect_affinity_misses(evs)
    assert len(found) == 1
    f = found[0]
    assert f.kind == AFFINITY_MISS and f.count == 4
    assert f.detail["miss_frac"] == 1.0
    # same placements executed in place: no findings
    clean = []
    for i in range(4):
        clean.append(_mk(1.0 + i, EV_READY, wd_id=i, slot=1,
                         label=f"a{i}", data="affine"))
        clean.append(_mk(2.0 + i, EV_START, wd_id=i, slot=1,
                         label=f"a{i}"))
    assert detect_affinity_misses(clean) == []
    # a miss without a steal is a benign re-pop, not a trade
    no_steal = [e for e in evs if e.ev != EV_STEAL]
    assert detect_affinity_misses(no_steal) == []


def test_detect_all_kwarg_routing():
    evs = _workers_present()
    for i in range(3):
        evs.append(_mk(1.0 + i * 0.01, EV_READY, wd_id=i, slot=1))
    evs.append(_mk(100.0, EV_END, wd_id=901, slot=1))
    assert detect_all(evs) == []                 # depth 3 < default 4
    found = detect_all(evs, starvation_depth_min=3)
    assert [f.kind for f in found] == [STARVATION]


# ------------------------------------------------- tuner feedback loop
def test_tuner_trace_hook_only_registered_when_traced():
    with TaskRuntime(num_workers=2, mode="sharded") as rt:
        DynamicTuner(rt)
        assert "trace-feedback" not in rt.dispatcher.stats()
    with TaskRuntime(num_workers=2, mode="sharded", trace=True) as rt:
        DynamicTuner(rt)
        rt.task(_spin)
        rt.taskwait()
        assert rt.dispatcher.stats()["trace-feedback"] >= 1


def test_tuner_starvation_votes_widen_and_unsettle():
    rt = TaskRuntime(num_workers=8, mode="sharded", trace=True)
    try:
        tuner = DynamicTuner(rt, TunerConfig(trace_starve_votes=2))
        tuner._shard_settled = True
        mgr0 = rt.params.max_ddast_threads
        starv = [Finding(STARVATION, 0.0, 1.0)]
        assert tuner.note_trace_verdicts(starv) is False   # 1st vote
        assert rt.params.max_ddast_threads == mgr0
        assert tuner.note_trace_verdicts(starv) is True    # 2nd: act
        assert rt.params.max_ddast_threads == mgr0 + 1
        assert tuner.shards_settled is False               # re-bracket
        acts = [a for _, a in tuner.trace_actions]
        assert acts == ["widen_managers", "unsettle_shards"]
        # the vote counter reset: the next lone verdict does nothing
        assert tuner.note_trace_verdicts(starv) is False
        # non-starvation verdicts are recorded but never move a knob
        n = len(tuner.trace_actions)
        tuner.note_trace_verdicts([Finding(AFFINITY_MISS, 0, 1)] * 5)
        assert len(tuner.trace_actions) == n
        assert len(tuner.trace_verdicts) == 8
    finally:
        rt.start()
        rt.shutdown()


def test_tuner_trace_callback_live_run():
    """End to end on real threads: the quiescence hook sweeps without
    error and only acts when the detectors actually voted."""
    rt = TaskRuntime(num_workers=4, mode="sharded", trace=True)
    tuner = DynamicTuner(rt)
    with rt:
        for it in range(2):
            for i in range(16):
                rt.task(_spin, deps=[(("r", i % 4), "inout")])
            rt.taskwait()
    assert isinstance(tuner.trace_verdicts, list)
    if not any(f.kind == STARVATION for f in tuner.trace_verdicts):
        assert tuner.trace_actions == []


# ------------------------------------------------- stats satellites
def test_worker_steals_surfaced_both_drivers():
    res = RuntimeSimulator(4, "ddast", trace=True).run(
        _chain_fanout_specs())
    assert len(res.worker_steals) == 4
    assert sum(res.worker_steals) == \
        sum(1 for e in res.events if e.ev == EV_STEAL)
    with TaskRuntime(num_workers=4, mode="ddast", trace=True) as rt:
        for i in range(24):
            rt.task(_spin, deps=[(("r", i % 4), "inout")])
        rt.taskwait()
    st = rt.stats
    assert len(st.worker_steals) == len(rt.placement.deques)
    assert sum(st.worker_steals) == \
        sum(1 for e in st.events if e.ev == EV_STEAL)
    assert st.load_cap_skips == 0            # round-robin has no cap


def test_load_cap_skips_counted_and_surfaced():
    pl = ShardAffinePlacement(2)
    hot = WorkDescriptor(func=None, deps=((("h",), INOUT),), label="w")
    pl.note_executed(hot, 0)                 # region pinned to slot 0
    for i in range(8):
        pl.push(WorkDescriptor(func=None, deps=((("h",), INOUT),),
                               label=f"w{i}"))
    assert pl.load_cap_skips > 0             # cap yielded to balance
    assert pl.stats()["load_cap_skips"] == pl.load_cap_skips
    res = RuntimeSimulator(4, "sharded", trace=True,
                           placement="shard_affine").run(
        sim_matmul_specs(6, dur_us=100))
    assert isinstance(res.load_cap_skips, int)


def test_scope_rollup_includes_steals():
    sim = RuntimeSimulator(4, "ddast", trace=True)
    res = sim.run_scopes(
        [_chain_fanout_specs(2, 2), _chain_fanout_specs(2, 2)],
        names=["a", "b"])
    for name in ("a", "b"):
        assert "steals" in res.scopes[name]
        assert res.scopes[name]["steals"] >= 0
    total = sum(res.scopes[n]["steals"] for n in ("a", "b"))
    scope_steal_events = sum(1 for e in res.events
                             if e.ev == EV_STEAL and e.scope is not None)
    assert total == scope_steal_events


def test_admission_defer_events_recorded():
    sim = RuntimeSimulator(4, "ddast", trace=True)
    res = sim.run_scopes(
        [_chain_fanout_specs(4, 3), _chain_fanout_specs(4, 3)],
        max_inflight=[1, 1], names=["a", "b"])
    defers = [e for e in res.events if e.ev == EV_ADMIT_DEFER]
    assert defers                            # cap 1 must hold tasks back
    assert all(e.scope is not None for e in defers)
    assert all(e.data["queued"] >= 1 for e in defers)


def test_sharded_mailbox_events_balance():
    """Every enqueued/delegated submit/done is eventually drained: the
    (kind, where, n) payloads sum to zero backlog at run end, per
    mailbox (blocking) or per shard request list (delegation)."""
    res = RuntimeSimulator(4, "sharded", trace=True).run(
        _chain_fanout_specs())
    backlog = {}
    for e in res.events:
        if e.ev in (EV_MSG_ENQ, EV_DELEGATE, EV_MSG_DRAIN):
            kind, where, n = e.data
            backlog[where] = backlog.get(where, 0) \
                + (-n if e.ev == EV_MSG_DRAIN else n)
    assert backlog and all(v == 0 for v in backlog.values())
    # deps_resolved is stamped per shard portion on multi-region tasks:
    # each head spans two regions, so 1 or 2 portions depending on
    # whether the region hashes collide on one shard
    per_head = Counter(e.label for e in res.events
                       if e.ev == EV_DEPS and e.label.startswith("head"))
    assert set(per_head) == {f"head{c}" for c in range(4)}
    assert all(1 <= n <= 2 for n in per_head.values())


# ------------------------------------------------------- traceview
def test_traceview_chrome_trace_structure(tmp_path):
    from repro.analysis import traceview

    res = RuntimeSimulator(4, "sharded", trace=True,
                           placement="shard_affine").run(
        _chain_fanout_specs(), iterations=2)
    p = tmp_path / "run.trace"
    save_trace(str(p), res.events, time_unit="us")
    out = traceview.main([str(p), "-o", str(tmp_path / "out.json"),
                          "--detect"])
    assert out == 0
    doc = json.loads((tmp_path / "out.json").read_text())
    evs = doc["traceEvents"]
    slices = [e for e in evs if e["ph"] == "X"]
    assert len(slices) == res.tasks          # one slice per body
    assert all(e["dur"] >= 0 for e in slices)
    assert all(e["pid"] == 0 for e in slices)
    names = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert any(n.startswith("worker") for n in names)
    counters = [e for e in evs if e["ph"] == "C"]
    assert counters and all(e["args"]["backlog"] >= 0 for e in counters)
    assert any(e["ph"] == "i" and e["name"] == "quiesce" for e in evs)
    assert doc["otherData"]["time_unit"] == "us"


def test_traceview_slice_pairing_survives_dropped_starts():
    """A ring that evicted a start event must not produce a negative
    or phantom slice."""
    from repro.analysis.traceview import to_chrome_trace
    evs = [_mk(5.0, EV_END, wd_id=1, slot=0, label="orphan"),
           _mk(6.0, EV_START, wd_id=2, slot=0, label="ok"),
           _mk(7.0, EV_END, wd_id=2, slot=0, label="ok")]
    doc = to_chrome_trace(evs, "us")
    slices = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert [s["name"] for s in slices] == ["ok"]


# ------------------------------------------------- spans and the switch
def test_recorder_spans_and_counts_round_trip(tmp_path):
    clock = iter([2.0, 3.5, 9.0])
    rec = TraceRecorder(2, clock=lambda: next(clock), time_unit="us")
    t = rec.span(SPAN_MANAGER, 1, 1.0, 4)        # ends at 2.0
    assert t == 2.0
    rec.span(SPAN_TRACK, 0, t)                   # 2.0 -> 3.5, no payload
    rec.count(COUNT_EMPTY_POLL, 1)
    rec.count(COUNT_EMPTY_POLL, 1)
    rec.count(COUNT_EMPTY_POLL, -1)              # overflow slot
    p = tmp_path / "spans.trace"
    rec.save(str(p))
    events, meta = load_trace(str(p))
    assert [(e.ev, e.label, e.slot, e.t, span_end(e), e.data[1])
            for e in events] == [(EV_SPAN, SPAN_MANAGER, 1, 1.0, 2.0, 4),
                                 (EV_SPAN, SPAN_TRACK, 0, 2.0, 3.5, None)]
    assert meta["counts"] == {COUNT_EMPTY_POLL: [0, 2, 1]}
    assert meta["origin"] is None                # a clock of its own


def test_recorder_origin_maps_back_to_perf_counter():
    before = time.perf_counter()
    rec = TraceRecorder(1)
    assert before <= rec.origin <= time.perf_counter()
    rec.origin = time.perf_counter()
    lo = time.perf_counter()
    rec.span(SPAN_ADMIT, 0, rec.clock())
    hi = time.perf_counter()
    (e,) = rec.events()
    assert lo <= rec.origin + e.t <= rec.origin + span_end(e) <= hi


def test_recorder_switch_records_only_while_on():
    with TaskRuntime(num_workers=2, mode="ddast", trace=True) as rt:
        rt.tracer.enabled = False
        for i in range(8):
            rt.task(_spin, deps=[(("r", i % 2), "inout")], label="off")
        rt.taskwait()
        assert rt.tracer.total_appended == 0
        rt.tracer.enabled = True
        for i in range(8):
            rt.task(_spin, deps=[(("r", i % 2), "inout")], label="on")
        rt.taskwait()
        rt.tracer.enabled = False
        n_on = rt.tracer.total_appended
        rt.task(_spin, label="off")
        rt.taskwait()
        assert rt.tracer.total_appended == n_on
    labels = {e.label for e in rt.stats.events if e.wd_id >= 0}
    assert labels == {"on"}
    assert sum(e.ev == EV_END for e in rt.stats.events) == 8


@pytest.mark.parametrize("producer", ["runtime", "engine"])
def test_untraced_producers_record_nothing(producer):
    if producer == "runtime":
        with TaskRuntime(num_workers=2, mode="ddast") as rt:
            for i in range(8):
                rt.task(_spin, deps=[(("r", i % 2), "inout")])
            rt.taskwait()
        tracer = rt.tracer
    else:
        from test_scopes import _StubModel
        from repro.serve.engine import Request, ServeEngine
        eng = ServeEngine(_StubModel(), None, batch_slots=2, max_len=8,
                          num_clients=1)
        eng.submit(Request(prompt=[1, 2], max_new_tokens=2))
        eng.run_until_drained()
        assert eng.steps > 0
        tracer = eng.tracer
    assert tracer is NULL_TRACER
    assert tracer.total_appended == 0 and tracer.counts() == {}


def test_manager_spans_and_drains_on_the_managing_slot():
    W = 3
    with TaskRuntime(num_workers=W, mode="ddast", trace=True) as rt:
        for i in range(60):
            rt.task(_spin, deps=[(("r", i % 6), "inout")], label=f"t{i}")
        rt.taskwait()
        counts = rt.tracer.counts()
    events = rt.stats.events
    spans = [e for e in events if e.ev == EV_SPAN]
    drains = [e for e in events if e.ev == EV_MSG_DRAIN]
    assert spans and all(e.label == SPAN_MANAGER for e in spans)
    # every slot is a real one: the workers' and the main thread's
    assert {e.slot for e in spans} <= set(range(W + 1))
    assert {e.slot for e in drains} <= set(range(W + 1))
    # ddast drains only inside manager sessions: payloads add up
    assert sum(e.data[1] for e in spans) == len(drains) == 120
    for e in spans:
        assert e.data[1] > 0 and span_end(e) >= e.t
    # per slot, sessions and task bodies never overlap (a flat graph)
    for s in range(W + 1):
        mine = sorted((e.t, span_end(e)) for e in spans if e.slot == s)
        starts = {e.wd_id: e.t for e in events
                  if e.ev == EV_START and e.slot == s}
        mine += sorted((starts[e.wd_id], e.t) for e in events
                       if e.ev == EV_END and e.slot == s)
        mine.sort()
        assert all(a[1] <= b[0] for a, b in zip(mine, mine[1:])), s
    assert sum(counts[COUNT_EMPTY_POLL]) > 0


def test_starvation_backlog_reads_drains_on_worker_slots():
    """Drains now carry the managing slot; the backlog signal counts
    them by payload whatever the slot, as before."""
    evs = _workers_present()
    evs.append(_mk(1.0, EV_MSG_ENQ, slot=2, data=("submit_batch", 2, 10)))
    evs.append(_mk(100.0, EV_MSG_DRAIN, slot=1,
                   data=("submit_batch", 2, 10)))
    found = detect_starvation(evs)
    assert len(found) == 1 and found[0].detail["backlog_only"]
    evs = _workers_present()
    evs.append(_mk(0.5, EV_MSG_ENQ, slot=2, data=("submit_batch", 2, 20)))
    t = 1.0
    for _ in range(120):
        evs.append(_mk(t, EV_MSG_ENQ, slot=2, data=("submit", 2, 1)))
        evs.append(_mk(t + 0.25, EV_MSG_DRAIN, slot=0,
                       data=("submit", 2, 1)))
        t += 0.5
    assert detect_starvation(evs) == []


def test_serve_engine_step_spans():
    from test_scopes import _StubModel
    from repro.serve.engine import Request, ServeEngine
    eng = ServeEngine(_StubModel(), None, batch_slots=2, max_len=8,
                      num_clients=1, trace=True)
    for _ in range(3):
        eng.submit(Request(prompt=[1, 2], max_new_tokens=2))
    eng.run_until_drained()
    events = eng.tracer.events()
    assert all(e.ev == EV_SPAN and e.slot == 0 for e in events)
    names = [e.label for e in events]
    assert names.count(SPAN_DISPATCH) == names.count(SPAN_READBACK) \
        == names.count(SPAN_TRACK) == eng.steps
    assert names.count(SPAN_ADMIT) >= eng.steps
    assert sum(e.data[1] for e in events if e.label == SPAN_ADMIT) == 3
    # the stub has no prefill_chunk: its prompts are teacher-forced
    assert SPAN_PREFILL not in names
    assert eng.stats["teacher_forced_tokens"] == 6
    # a step's spans follow each other without a gap
    for a, b in zip(events, events[1:]):
        if b.label != SPAN_ADMIT:
            assert (a.label, span_end(a)) == (
                {SPAN_DISPATCH: SPAN_ADMIT, SPAN_READBACK: SPAN_DISPATCH,
                 SPAN_TRACK: SPAN_READBACK}[b.label], b.t)


def test_serve_engine_prefill_spans():
    import jax
    from repro.configs import tiny_config
    from repro.models.registry import get_model
    from repro.serve.engine import Request, ServeEngine
    model = get_model(tiny_config("qwen2-0.5b"))
    eng = ServeEngine(model, model.init_params(jax.random.key(0)),
                      batch_slots=2, max_len=16, num_clients=1, trace=True)
    for n in (3, 16, 9):
        eng.submit(Request(prompt=list(range(1, n + 1)), max_new_tokens=2))
    eng.run_until_drained()
    events = eng.tracer.events()
    prefill = [e for e in events if e.label == SPAN_PREFILL]
    # max_len 16 caps the chunk at 16 tokens: one chunk a prompt
    assert len(prefill) == eng.stats["prefill_chunks"] == 3
    assert sum(e.data[1] for e in prefill) \
        == eng.stats["prefill_tokens"] == 28
    # each chunk's span sits between the step's admission and dispatch
    for a, b, c in zip(events, events[1:], events[2:]):
        if b.label == SPAN_PREFILL:
            assert (a.label, c.label) == (SPAN_ADMIT, SPAN_DISPATCH)
            assert span_end(a) == b.t and span_end(b) == c.t


def test_traceview_draws_spans_as_slices():
    from repro.analysis.traceview import to_chrome_trace
    evs = [_mk(1.0, EV_SPAN, slot=2, label=SPAN_MANAGER, data=(1.5, 3)),
           _mk(2.0, EV_SPAN, slot=0, label=SPAN_TRACK, data=(2.25, None))]
    doc = to_chrome_trace(evs, "s")
    slices = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert [(s["name"], s["tid"], s["ts"], s["dur"], s["args"])
            for s in slices] == [
        (SPAN_MANAGER, 2, 1e6, 5e5, {"data": 3}),
        (SPAN_TRACK, 0, 2e6, 2.5e5, {})]


def test_recorder_events_while_producers_append():
    """A live reader (the metrics sampler's sweep) merges the rings while
    other threads append to them: no 'deque mutated' error, and every
    event read is whole."""
    import sys
    import threading
    rec = TraceRecorder(2, capacity=1 << 10)
    stop = threading.Event()

    def produce(slot):
        while not stop.is_set():
            rec.mgr_event(EV_MSG_ENQ, slot, ("submit", slot, 1))

    threads = [threading.Thread(target=produce, args=(s % 3 - 1,))
               for s in range(4)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        sweeps = 0
        until = time.perf_counter() + 0.5
        while time.perf_counter() < until:
            assert all(e.data[0] == "submit" for e in rec.events())
            sweeps += 1
    finally:
        stop.set()
        sys.setswitchinterval(old)
        for t in threads:
            t.join(timeout=5)
    assert not any(t.is_alive() for t in threads)
    assert sweeps > 0 and rec.total_appended > 0
