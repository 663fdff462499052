"""Live metrics plane (core.metrics): histogram bucket/merge/quantile
properties, per-slot instruments and the metrics-off no-op contract,
sampler lifecycle across every policy on the threads, process and
simulated drivers, per-scope SLO attainment (including the expiry
path), the shm counter plane's totals + leak discipline, the
Prometheus/Perfetto exporters, the ``metricsview`` CLI and the
``traceview --counters`` merge, and the incremental detector's
agreement with the post-hoc pipeline."""
import json
import random
import threading
import time
import urllib.request

import pytest

from repro.core import RuntimeSimulator, SimTaskSpec, TaskRuntime
from repro.core.errors import ScopeExpired
from repro.core.metrics import (LogHistogram, MetricsHub, NULL_METRICS,
                                counter_track_events, prometheus_text,
                                save_metrics)
from repro.core.trace import (EV_END, EV_READY, EV_START, STARVATION,
                              IncrementalDetector, TraceEvent,
                              detect_all)

ALL_MODES = ("sync", "dast", "ddast", "sharded")


def _spin(n: int = 500) -> int:
    s = 0
    for i in range(n):
        s += i
    return s


# ------------------------------------------------- histogram properties
def test_histogram_bucket_monotonicity():
    """Bucket bounds tile the axis: contiguous, strictly increasing,
    and every recorded value lands in the bucket that contains it."""
    h = LogHistogram(1.0)
    prev_hi = 0.0
    for idx in range(256):
        lo, hi = h._bounds(idx)
        assert lo < hi
        assert lo == prev_hi          # no gap, no overlap
        prev_hi = hi
    for v in [0, 1, 3, 4, 7, 8, 100, 12345, 1 << 20]:
        lo, hi = h._bounds(h._index(v))
        assert lo <= v < hi


def test_histogram_merge_associative_commutative():
    rng = random.Random(7)
    hs = [LogHistogram(1e-3) for _ in range(3)]
    for h in hs:
        for _ in range(200):
            h.record(rng.uniform(0, 50.0))
    a, b, c = hs
    left = a.merge(b).merge(c)
    right = a.merge(b.merge(c))
    assert left.counts == right.counts
    assert left.count == right.count == 600
    assert left.total == pytest.approx(right.total)
    ab, ba = a.merge(b), b.merge(a)
    assert ab.counts == ba.counts and ab.min == ba.min and ab.max == ba.max
    with pytest.raises(ValueError):
        a.merge(LogHistogram(1.0))    # resolutions must match


def test_histogram_quantile_bounds():
    """quantile(q) is conservative: >= the exact q-quantile, and within
    the documented 25% + resolution envelope above it."""
    rng = random.Random(11)
    vals = [rng.uniform(0, 1000.0) for _ in range(500)]
    h = LogHistogram(0.01)
    for v in vals:
        h.record(v)
    svals = sorted(vals)
    for q in (0.1, 0.5, 0.9, 0.99, 1.0):
        exact = svals[min(int(q * len(svals) + 0.999999), len(svals)) - 1]
        got = h.quantile(q)
        assert got >= exact - 1e-9
        assert got <= exact * 1.25 + h.resolution + 1e-9
    assert LogHistogram(1.0).quantile(0.5) == 0.0


def test_histogram_snapshot_roundtrip_sums():
    h = LogHistogram(1.0)
    for v in (1, 5, 5, 300):
        h.record(v)
    snap = h.snapshot()
    assert snap["count"] == 4 and snap["sum"] == 311
    assert sum(n for _, _, n in snap["buckets"]) == 4
    for lo, hi, _ in snap["buckets"]:
        assert lo < hi


# --------------------------------------------- instruments + off path
def test_metrics_hub_slots_and_overflow():
    hub = MetricsHub(2, clock=time.perf_counter)
    hub.task_start(0)
    hub.task_end(0, 0.5)
    hub.task_start(99)                # out of range -> overflow slot
    hub.task_end(-3, 0.25)
    snap = hub.snapshot()
    assert snap["counters"]["tasks_started"]["per_slot"] == [1, 0, 1]
    assert snap["counters"]["tasks_finished"]["total"] == 2
    assert snap["task_latency"]["count"] == 2


def test_metrics_disabled_is_the_null_singleton():
    """metrics=False must leave the hot path with exactly one shared
    no-op object: no sampler registered, no per-runtime instrument
    state, empty stats.metrics — the structural no-op-cost guarantee
    (one ``.enabled`` check, zero writes)."""
    with TaskRuntime(num_workers=2, mode="ddast") as rt:
        rt.task(_spin)
        rt.taskwait()
        assert rt.instruments is NULL_METRICS
        assert not rt.instruments.enabled
        assert rt.sampler is None
        names = [c.name for c in rt.dispatcher._callbacks]
        assert "metrics-sampler" not in names
    assert rt.stats.metrics == {}
    assert NULL_METRICS.snapshot() == {}
    NULL_METRICS.task_start(0)        # no-ops, no state
    NULL_METRICS.task_end(0, 1.0)
    assert NULL_METRICS.snapshot() == {}


# -------------------------------------------- threads driver lifecycle
@pytest.mark.parametrize("mode", ALL_MODES)
def test_threads_metrics_lifecycle(mode):
    """Every policy: counters track tasks exactly, the sampler runs,
    and a second burst after a taskwait keeps counting (no freeze at
    quiescence)."""
    with TaskRuntime(num_workers=2, mode=mode, metrics=True,
                     metrics_interval_s=1e-4) as rt:
        for i in range(20):
            rt.task(_spin, label=f"a{i}")
        rt.taskwait()
        mid = rt.metrics()
        assert mid["counters"]["tasks_finished"]["total"] == 20
        for i in range(10):
            rt.task(_spin, label=f"b{i}")
        rt.taskwait()
    m = rt.stats.metrics
    assert m["counters"]["tasks_started"]["total"] == 30
    assert m["counters"]["tasks_finished"]["total"] == 30
    assert m["task_latency"]["count"] == 30
    assert m["sampler"]["samples"] >= 2   # quiescence ticks at minimum
    assert "ready" in m["sampler"]["series"]


def test_threads_metrics_concurrent_reader():
    """rt.metrics() is safe to hammer from another thread while the
    run is in flight (lock-free reads of single-writer state)."""
    stop = threading.Event()
    seen = []

    with TaskRuntime(num_workers=2, mode="sharded", metrics=True,
                     metrics_interval_s=1e-4) as rt:
        def reader():
            while not stop.is_set():
                seen.append(rt.metrics()["counters"]
                            ["tasks_finished"]["total"])

        t = threading.Thread(target=reader)
        t.start()
        try:
            for i in range(200):
                rt.task(time.sleep, 1e-5, label=f"t{i}")
            rt.taskwait()
        finally:
            stop.set()
            t.join()
    assert seen and seen == sorted(seen)  # monotonic counter reads
    assert rt.stats.metrics["counters"]["tasks_finished"]["total"] == 200


# ------------------------------------------------------- SLO attainment
def test_scope_slo_attainment_met():
    with TaskRuntime(num_workers=2, mode="ddast", num_clients=1,
                     metrics=True) as rt:
        sc = rt.open_scope("tenantA", deadline=30.0)
        for i in range(12):
            sc.task(_spin, label=f"t{i}")
        sc.taskwait()           # scope tasks are not the root's children
        live = rt.metrics()["scopes"]["tenantA"]["slo"]
        assert live["met"] == 12 and live["missed"] == 0
        assert live["attainment"] == 1.0
        assert live["slack"]["count"] == 12
    rolled = rt.stats.scopes["tenantA"]["slo"]
    assert rolled["met"] == 12 and rolled["attainment"] == 1.0


def test_scope_slo_expiry_counts_misses():
    """A scope that blows its deadline: queued tasks drain cancelled
    (missed, no slack sample), taskwait raises ScopeExpired, and the
    rollup still reports the attainment split."""
    rt = TaskRuntime(num_workers=1, mode="ddast", num_clients=1,
                     metrics=True)
    rt.start()
    sc = rt.open_scope("tenantB", deadline=0.08)
    for i in range(30):
        sc.task(time.sleep, 0.02, label=f"slow{i}")
    with pytest.raises(ScopeExpired, match="deadline"):
        sc.taskwait()
    slo = sc.slo_snapshot()
    assert slo["missed"] > 0
    assert slo["attainment"] is None or slo["attainment"] < 1.0
    # cancelled tasks contribute no slack sample
    assert slo["slack"]["count"] <= slo["met"] + slo["missed"]
    rt.shutdown()
    entry = rt.stats.scopes["tenantB"]
    assert entry["slo"]["missed"] > 0


# ----------------------------------------------------- process backend
def test_procs_metrics_plane_totals_and_no_leak():
    with TaskRuntime(4, backend="processes", metrics=True,
                     metrics_interval_s=1e-3) as rt:
        for i in range(48):
            rt.task(_spin, 2000, label=f"t{i}")
        rt.taskwait()
        live = rt.metrics()
        assert live["workers"]["totals"]["tasks_finished"] == 48.0
        assert len(live["workers"]["per_worker"]) == 4
        assert live["sampler"]["samples"] >= 1
    m = rt.stats.metrics
    assert m["workers"]["totals"]["tasks_started"] == 48.0
    assert m["workers"]["totals"]["exec_time_s"] > 0.0
    assert m["gauges"]["ipc_done_msgs"] > 0
    assert rt.leaked_shm == []        # plane unlinked with the rings


@pytest.mark.parametrize("mode", ("sync", "sharded"))
def test_procs_metrics_lifecycle_modes(mode):
    with TaskRuntime(2, backend="processes", mode=mode,
                     metrics=True) as rt:
        for i in range(16):
            rt.task(_spin, 1000, label=f"t{i}")
        rt.taskwait()
    totals = rt.stats.metrics["workers"]["totals"]
    assert totals["tasks_finished"] == 16.0
    assert rt.leaked_shm == []


# ----------------------------------------------------------- simulator
def test_sim_metrics_counters_and_priced_overhead():
    specs = [SimTaskSpec(dur=100.0, label=f"t{i}") for i in range(64)]
    base = RuntimeSimulator(num_cores=4, mode="ddast").run(specs)
    r = RuntimeSimulator(num_cores=4, mode="ddast", metrics=True,
                         metrics_interval_us=50.0).run(specs)
    assert r.metrics["counters"]["tasks_finished"]["total"] == 64
    assert r.metrics["task_latency"]["count"] == 64
    samp = r.metrics["sampler"]
    assert samp["samples"] >= 2
    assert any(k.startswith("ready_depth.") for k in samp["series"])
    # every instrument write and sampler tick is priced in virtual time
    assert r.makespan_us > base.makespan_us
    assert base.metrics == {}


def test_sim_metrics_scopes_admission_series():
    specs = [SimTaskSpec(dur=50.0, label=f"t{i}") for i in range(32)]
    r = RuntimeSimulator(num_cores=2, mode="ddast", metrics=True,
                         metrics_interval_us=25.0).run_scopes(
        [specs, specs], weights=[2.0, 1.0])
    series = r.metrics["sampler"]["series"]
    assert "admission_backlog" in series
    assert "admission_waits" in series


# ------------------------------------------------------------ exporters
def _threads_snapshot():
    with TaskRuntime(num_workers=2, mode="ddast", num_clients=1,
                     metrics=True, metrics_interval_s=1e-4,
                     trace=True) as rt:
        sc = rt.open_scope("tenantA", deadline=30.0)
        for i in range(16):
            sc.task(_spin, label=f"t{i}")
        rt.taskwait()
    return rt


def test_prometheus_text_exposition():
    rt = _threads_snapshot()
    txt = prometheus_text(rt.stats.metrics)
    assert '# TYPE repro_tasks_finished_total counter' in txt
    assert 'repro_tasks_finished_total{slot="0"}' in txt
    assert '# TYPE repro_task_latency_seconds histogram' in txt
    assert 'repro_task_latency_seconds_count 16' in txt
    assert 'repro_scope_slo_attainment{scope="tenantA"} 1' in txt
    assert 'repro_scope_slack_seconds_bucket{scope="tenantA",le=' in txt
    assert 'repro_sampled{series=' in txt
    # cumulative le-buckets are monotone nondecreasing
    cums = [int(line.rsplit(" ", 1)[1]) for line in txt.splitlines()
            if line.startswith("repro_task_latency_seconds_bucket")]
    assert cums == sorted(cums) and cums[-1] == 16


def test_counter_track_events_shape():
    rt = _threads_snapshot()
    series = rt.stats.metrics["sampler"]["series"]
    evs = counter_track_events(series, "s")
    assert evs[0]["ph"] == "M"        # process_name meta leads
    counters = [e for e in evs if e["ph"] == "C"]
    assert counters
    for e in counters:
        assert set(e) >= {"name", "pid", "tid", "ts", "args"}
        assert "value" in e["args"]
    # seconds scale to Chrome-trace microseconds
    t, v = next(iter(series.values()))[0]
    assert any(abs(e["ts"] - t * 1e6) < 1e-3 for e in counters)


def test_metricsview_cli_and_traceview_counters(tmp_path):
    from repro.analysis.metricsview import main as metricsview
    from repro.analysis.traceview import main as traceview
    rt = _threads_snapshot()
    mpath = tmp_path / "run.metrics.json"
    tpath = tmp_path / "run.trace"
    save_metrics(str(mpath), rt.stats.metrics)
    rt.tracer.save(str(tpath))

    prom = tmp_path / "prom.txt"
    assert metricsview([str(mpath), "-o", str(prom)]) == 0
    assert "repro_scope_slo_attainment" in prom.read_text()

    perf = tmp_path / "ctr.json"
    assert metricsview([str(mpath), "--perfetto", "-o", str(perf)]) == 0
    doc = json.loads(perf.read_text())
    assert any(e["ph"] == "C" for e in doc["traceEvents"])

    merged = tmp_path / "merged.json"
    assert traceview([str(tpath), "-o", str(merged),
                      "--counters", str(mpath)]) == 0
    doc = json.loads(merged.read_text())
    slices = [e for e in doc["traceEvents"]
              if e.get("ph") == "X" and e.get("cat") == "task"]
    counters = [e for e in doc["traceEvents"] if e.get("ph") == "C"
                and e["name"] in rt.stats.metrics["sampler"]["series"]]
    assert slices and counters        # both layers in one document


# --------------------------------------------------- incremental detect
def _mk(t, ev, wd_id=-1, slot=-1, label="", scope=None, data=None):
    return TraceEvent(t, ev, wd_id, slot, label, scope, data)


def _starvation_events():
    evs = [_mk(0.0, EV_START, 900, 0, "warm"),
           _mk(0.1, EV_END, 900, 0, "warm"),
           _mk(0.0, EV_START, 901, 1, "warm"),
           _mk(0.1, EV_END, 901, 1, "warm")]
    for i in range(5):
        evs.append(_mk(1.0 + i * 0.01, EV_READY, i, 1, f"t{i}"))
    evs.append(_mk(100.0, EV_END, 901, 1))
    return evs


def test_incremental_detector_agrees_with_posthoc():
    evs = _starvation_events()
    posthoc = detect_all(evs)
    assert any(f.kind == STARVATION for f in posthoc)
    det = IncrementalDetector()
    live = []
    for cut in range(1, len(evs) + 1):
        live.extend(det.sweep(evs[:cut]))
    key = lambda f: (f.kind, round(f.t0, 9), f.slot)  # noqa: E731
    assert {key(f) for f in live} == {key(f) for f in posthoc}
    assert len(live) == len({key(f) for f in live})   # deduplicated
    assert det.sweep(evs) == []       # nothing fresh on a re-sweep
    assert [key(f) for f in det.findings] == [key(f) for f in live]


def test_sampler_sweeps_feed_live_findings():
    """A traced metrics runtime accumulates live findings through its
    sampler without waiting for the post-hoc pipeline."""
    with TaskRuntime(num_workers=2, mode="ddast", metrics=True,
                     metrics_interval_s=1e-4, trace=True) as rt:
        assert rt.sampler.detector is not None
        for i in range(40):
            rt.task(_spin, label=f"t{i}")
        rt.taskwait()
        swept = rt.sampler._trace_seen
    assert swept > 0                  # the live window was examined
    # live findings are deduplicated (the incremental detector never
    # re-reports a verdict it already surfaced) and every one rides the
    # read-side snapshot. Exact live-vs-posthoc agreement is pinned on
    # a deterministic timeline in
    # test_incremental_detector_agrees_with_posthoc — a real wall-clock
    # run's mid-span sweeps may legitimately flag transient spans the
    # full-span pass dilutes away.
    key = lambda f: (f.kind, round(f.t0, 9), f.slot)  # noqa: E731
    live = rt.sampler.live_findings
    assert len({key(f) for f in live}) == len(live)
    assert len(rt.sampler.snapshot()["live_findings"]) == len(live)


# ------------------------------------------------------------ serving
def test_serve_engine_metrics_and_scrape():
    from test_scopes import _StubModel
    from repro.serve.engine import Request, ServeEngine
    with TaskRuntime(num_workers=2, mode="ddast", num_clients=2) as rt:
        eng = ServeEngine(_StubModel(), None, batch_slots=2, max_len=8,
                          num_clients=2, runtime=rt,
                          client_deadlines=[30.0, None])
        for c in range(2):
            for _ in range(3):
                eng.submit(Request(prompt=[1, 2], max_new_tokens=2),
                           client_id=c)
        eng.run_until_drained()
        snap = eng.metrics_snapshot()
        c0 = snap["clients"]["client0"]
        assert c0["latency_steps"]["count"] == 3
        assert c0["slo"]["met"] == 3 and c0["slo"]["attainment"] == 1.0
        assert "slo" not in snap["clients"]["client1"]
        txt = eng.metrics_text()
        assert ('repro_request_latency_steps_count{client="client0"} 3'
                in txt)
        assert 'repro_client_slo_attainment{client="client0"} 1' in txt
        srv, port = eng.serve_metrics()
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10
            ).read().decode()
        finally:
            srv.shutdown()
        assert 'repro_request_latency_steps' in body
