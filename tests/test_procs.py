"""Process backend (core.procs): the processes-vs-serial oracle on the
three paper apps (exact float equality — the kernels are
multiply-accumulate chains, so any dependence-ordering violation changes
the result), dependence-order verification from worker-stamped exec
spans, replay steady-state 0-message checks across the process boundary,
trace-ring merge schema agreement with the threaded driver, worker-death
and body-error propagation, shm-ring wraparound/fallback behavior, wire
codec roundtrips, SimCosts IPC knobs, and clean shutdown with no leaked
shared-memory segments."""
import os
import pickle
import signal

import pytest

from repro.core import (ProcessRuntime, ShmRing, SimCosts, TaskFailed,
                        TaskRuntime, WorkerLost)
from repro.core.engine.charge import SimCharger
from repro.core.messages import (DONE_ERROR, DONE_OK, decode_done_batch,
                                 decode_submit_batch, encode_done_batch,
                                 encode_submit_batch)
from repro.core.procs import apps
from repro.core.trace import EV_CREATED, EV_END, EV_START

PROC_MODES = ("sync", "dast", "ddast", "sharded")


def _drain(shms):
    for s in shms:
        s.close_unlink()


def _assert_no_leaks(rt):
    names = rt.shm_names()
    rt.shutdown()
    leaked = [n for n in names if os.path.exists("/dev/shm/" + n)]
    assert not leaked, f"leaked shm segments: {leaked}"


# ------------------------------------------------------------ oracles
def _oracle_matmul(mode, replay=False, iterations=1):
    N, bs = 3, 3
    A = apps.ShmArray((N * bs) ** 2)
    B = apps.ShmArray((N * bs) ** 2)
    C = apps.ShmArray((N * bs) ** 2)
    C2 = apps.ShmArray((N * bs) ** 2)
    apps.fill_deterministic(A, 3)
    apps.fill_deterministic(B, 5)
    try:
        rt = ProcessRuntime(num_workers=2, mode=mode, replay=replay)
        with rt:
            for _ in range(iterations):
                calls = apps.submit_matmul(rt, A.name, B.name, C.name,
                                           N, bs)
                rt.taskwait()
        for _ in range(iterations):
            apps.run_serial([(f, tuple([a[0], a[1], C2.name] + list(a[3:])),
                              d, l) for f, a, d, l in calls])
        assert C.tolist() == C2.tolist()
        return rt
    finally:
        _drain([A, B, C, C2])


@pytest.mark.parametrize("mode", ["sync", "sharded"])
def test_matmul_matches_serial(mode):
    rt = _oracle_matmul(mode)
    assert rt.stats.tasks_executed == 27


def test_sparselu_matches_serial():
    nb, bs = 4, 3
    M = apps.ShmArray(nb * nb * bs * bs)
    M2 = apps.ShmArray(nb * nb * bs * bs)
    apps.fill_deterministic(M, 11)
    apps.fill_deterministic(M2, 11)
    try:
        with ProcessRuntime(num_workers=2, mode="sharded") as rt:
            calls = apps.submit_sparselu(rt, M.name, nb, bs)
            rt.taskwait()
        apps.run_serial([(f, tuple([M2.name] + list(a[1:])), d, l)
                         for f, a, d, l in calls])
        assert M.tolist() == M2.tolist()
    finally:
        _drain([M, M2])


def test_nbody_matches_serial():
    n = 8
    arrs = [apps.ShmArray(n) for _ in range(6)]
    P, V, A, P2, V2, A2 = arrs
    apps.fill_deterministic(P, 2)
    apps.fill_deterministic(P2, 2)
    try:
        with ProcessRuntime(num_workers=2, mode="ddast") as rt:
            calls = apps.submit_nbody(rt, P.name, V.name, A.name, n,
                                      steps=2)
            rt.taskwait()
        apps.run_serial([(f, tuple([{P.name: P2.name, V.name: V2.name,
                                     A.name: A2.name}.get(x, x)
                                    for x in a]), d, l)
                         for f, a, d, l in calls])
        assert P.tolist() == P2.tolist()
        assert V.tolist() == V2.tolist()
    finally:
        _drain(arrs)


def test_dependence_order_from_exec_spans():
    """Worker-stamped exec spans must respect every region edge:
    pred.t_end <= succ.t_start (one monotonic clock across processes)."""
    n = 6
    P, V, A = (apps.ShmArray(n) for _ in range(3))
    apps.fill_deterministic(P, 4)
    try:
        wds = []
        with ProcessRuntime(num_workers=2, mode="sharded") as rt:
            all_pos = [(("P", j), "in") for j in range(n)]
            for s in range(2):
                for i in range(n):
                    wds.append(rt.task(
                        apps.nbody_force, P.name, A.name, n, i,
                        deps=all_pos + [(("A", i), "out")],
                        label=f"force[{s},{i}]"))
                for i in range(n):
                    wds.append(rt.task(
                        apps.nbody_update, P.name, V.name, A.name, i,
                        deps=[(("A", i), "in"), (("V", i), "inout"),
                              (("P", i), "inout")],
                        label=f"update[{s},{i}]"))
            rt.taskwait()
        span = {wd.label: wd.exec_span for wd in wds}
        for s in range(2):
            for i in range(n):
                force_end = span[f"force[{s},{i}]"][1]
                upd_start = span[f"update[{s},{i}]"][0]
                assert force_end <= upd_start
                if s:
                    # update[s-1, j] writes P[j], force[s, i] reads all P
                    for j in range(n):
                        assert span[f"update[{s-1},{j}]"][1] <= \
                            span[f"force[{s},{i}]"][0]
    finally:
        _drain([P, V, A])


# ------------------------------------------------------------ replay
def test_replay_steady_state_zero_ipc():
    A = apps.ShmArray(8)
    apps.fill_deterministic(A, 9)
    ref = apps.ShmArray(8)
    apps.fill_deterministic(ref, 9)
    iters = 6
    try:
        with ProcessRuntime(num_workers=2, mode="sharded",
                            replay=True) as rt:
            for _ in range(iters):
                calls = []
                for i in range(10):
                    args = (A.name, A.name, A.name, i % 4)
                    calls.append((apps.nbody_update, args, None, None))
                    rt.task(apps.nbody_update, *args,
                            deps=[(("X", i % 4), "inout")], label=f"t{i}")
                rt.taskwait()
        # iteration 0 records (live mailbox traffic); every later
        # iteration runs on the shared replay plane: 0 Submit/Done
        # frames cross the process boundary
        assert rt.iter_ipc[0][0] > 0
        for sub, done in rt.iter_ipc[1:iters]:
            assert (sub, done) == (0, 0)
        assert rt.stats.replay_iterations >= iters - 2
        # and the data plane stayed correct through the replays
        for _ in range(iters):
            apps.run_serial([(f, (ref.name, ref.name, ref.name, a[3]),
                              None, None) for f, a, _d, _l in calls])
        assert A.tolist() == ref.tolist()
    finally:
        _drain([A, ref])


def test_replay_divergence_falls_back_live():
    A = apps.ShmArray(4)
    try:
        with ProcessRuntime(num_workers=1, mode="sharded",
                            replay=True) as rt:
            for it in range(4):
                n = 4 if it < 2 else 6      # structure changes at it=2
                for i in range(n):
                    rt.task(apps.nbody_update, A.name, A.name, A.name,
                            i % 2, deps=[(("X", i % 2), "inout")],
                            label=f"t{i}")
                rt.taskwait()
            assert rt.stats.tasks_executed == 4 + 4 + 6 + 6
    finally:
        _drain([A])


# ------------------------------------------------------------ traces
def test_trace_schema_agrees_with_threads():
    """Same workload, both drivers, trace=True: the merged event lists
    agree on the lifecycle multiset per label, worker events land on
    worker slots, and both are time-sorted."""
    def run(backend):
        A = apps.ShmArray(4)
        try:
            with TaskRuntime(num_workers=2, mode="sharded", trace=True,
                             backend=backend) as rt:
                for i in range(8):
                    rt.task(apps.nbody_update, A.name, A.name, A.name,
                            i % 2, deps=[(("X", i % 2), "inout")],
                            label=f"t{i}")
                rt.taskwait()
            return rt.stats.events
        finally:
            _drain([A])

    evs_t = run("threads")
    evs_p = run("processes")
    lifecycle = (EV_CREATED, EV_START, EV_END)

    def sig(evs):
        return sorted((e.label, e.ev) for e in evs
                      if e.ev in lifecycle and e.label.startswith("t"))

    assert sig(evs_t) == sig(evs_p)
    for evs in (evs_t, evs_p):
        assert [e.t for e in evs] == sorted(e.t for e in evs)
    # process-backend bodies run on worker slots (2 + widx)
    for e in evs_p:
        if e.ev in (EV_START, EV_END) and e.label.startswith("t"):
            assert e.slot >= 2


# ------------------------------------------------------------ failures
def _kill_self():
    os.kill(os.getpid(), signal.SIGKILL)


def _raise_value_error():
    raise ValueError("intentional kernel failure")


def test_worker_death_raises_worker_lost():
    rt = ProcessRuntime(num_workers=2, mode="sharded")
    rt.start()
    rt.task(_kill_self, label="victim")
    with pytest.raises(WorkerLost, match="victim"):
        rt.taskwait()
    rt.shutdown()                        # must not hang


def test_body_error_raises_task_failed():
    rt = ProcessRuntime(num_workers=1, mode="sync")
    rt.start()
    rt.task(_raise_value_error, label="bad")
    with pytest.raises(TaskFailed, match="intentional kernel failure"):
        rt.taskwait()
    rt.shutdown()


def test_unpicklable_task_rejected():
    with ProcessRuntime(num_workers=1) as rt:
        with pytest.raises(ValueError, match="picklable"):
            rt.task(lambda: None, label="lam")
        rt.taskwait()


# ------------------------------------------------------------ lifecycle
@pytest.mark.parametrize("mode", PROC_MODES)
def test_clean_shutdown_no_shm_leaks(mode):
    for _ in range(3):
        rt = ProcessRuntime(num_workers=2, mode=mode, replay=True)
        rt.start()
        for i in range(6):
            rt.task(apps.spin, 10.0, deps=[(("R", i % 2), "inout")],
                    label=f"s{i}")
        rt.taskwait()
        _assert_no_leaks(rt)


def test_results_round_trip():
    with ProcessRuntime(num_workers=1) as rt:
        wd = rt.task(sum, (1, 2, 3), label="sum")
        rt.taskwait()
        assert wd.result == 6


def test_backend_dispatch_and_validation():
    rt = TaskRuntime(num_workers=1, backend="processes")
    assert isinstance(rt, ProcessRuntime)
    rt.start()
    rt.shutdown()
    with pytest.raises(ValueError, match="backend"):
        TaskRuntime(backend="sidecars")
    with pytest.raises(TypeError):       # backend is keyword-only
        TaskRuntime(1, "sync", None, False, None, None, None,
                    "round_robin", False, 0, True, "processes")
    with pytest.raises(ValueError, match="scopes"):
        ProcessRuntime(num_clients=2)
    with pytest.raises(ValueError, match="mode"):
        ProcessRuntime(mode="warp")


def test_refuses_to_fork_once_tpu_backend_is_up(monkeypatch):
    """One process per chip: once this process has brought up JAX's TPU
    backend, start() raises before forking anything."""
    from jax._src import xla_bridge
    monkeypatch.setitem(xla_bridge._backends, "tpu", object())
    rt = ProcessRuntime(num_workers=2, mode="sharded")
    try:
        with pytest.raises(RuntimeError, match="TPU backend"):
            rt.start()
    finally:
        if rt._started:                  # forked after all: clean up
            rt.shutdown()
    assert not rt._procs and not rt.shm_names()
    monkeypatch.undo()                   # no TPU: the same runtime forks
    rt.start()
    _assert_no_leaks(rt)


# ------------------------------------------------------------ rings
def test_ring_wraparound():
    ring = ShmRing(capacity=256)
    try:
        payload = bytes(range(64))
        for _ in range(50):              # forces many wraps
            assert ring.try_push(payload)
            assert ring.pop() == payload
        assert ring.pop() is None
    finally:
        ring.close()
        ring.unlink()


def test_ring_fifo_and_backpressure():
    ring = ShmRing(capacity=256)
    try:
        frames = [bytes([i]) * 20 for i in range(14)]
        pushed = [f for f in frames if ring.try_push(f)]
        assert len(pushed) < len(frames)            # filled up
        assert ring.try_push(frames[0]) is False    # full: rejected
        assert [ring.pop() for _ in pushed] == pushed
    finally:
        ring.close()
        ring.unlink()


def test_ring_oversize_falls_back_in_order():
    import queue

    class FakeQueue:
        def __init__(self):
            self.q = queue.SimpleQueue()
        put = property(lambda s: s.q.put)
        get = property(lambda s: s.q.get)

    fb = FakeQueue()
    ring = ShmRing(capacity=256, fallback=fb)
    try:
        big = b"B" * 200                 # > capacity // 2: fallback lane
        ring.push(b"first")
        ring.push(big)
        ring.push(b"last")
        assert ring.pop() == b"first"
        assert ring.pop() == big         # FIFO preserved via marker
        assert ring.pop() == b"last"
        assert ring.fallbacks == 1
    finally:
        ring.close()
        ring.unlink()


def test_ring_attach_reads_header_capacity():
    ring = ShmRing(capacity=256)
    try:
        peer = ShmRing.attach(ring.name)
        # logical capacity comes from the header, never from shm.size
        # (page-rounded on some platforms)
        assert peer.capacity == ring.capacity == 256
        peer.close()
    finally:
        ring.close()
        ring.unlink()


def test_fallback_timeout_orphans_nothing():
    import queue

    fb = queue.SimpleQueue()
    ring = ShmRing(capacity=64, fallback=fb)
    try:
        while ring.try_push(b"x" * 8):   # 16-byte frames pack the ring
            pass                         # solid: no room for a marker
        big = b"B" * 60                  # oversize: fallback lane only
        assert ring._push_fallback(big, spin_s=0.01) is False
        assert fb.empty()                # timed out without enqueueing
        with pytest.raises(BufferError):
            ring.push(big, spin_s=0.01)  # retries may not double-enqueue
        assert fb.empty()
        assert ring.fallbacks == 0
    finally:
        ring.close()
        ring.unlink()


def test_push_waits_for_slow_but_live_consumer():
    ring = ShmRing(capacity=64)
    try:
        while ring.try_push(b"x" * 8):
            pass
        ring.consumer_alive = lambda: False
        with pytest.raises(BufferError):
            ring.push(b"y" * 8, spin_s=0.01)

        def probe():                     # live consumer making progress
            ring.pop()
            return True

        ring.consumer_alive = probe
        ring.push(b"y" * 8, spin_s=0.01)    # pre-fix: BufferError
        last = None
        while True:
            frame = ring.pop()
            if frame is None:
                break
            last = frame
        assert last == b"y" * 8
    finally:
        ring.close()
        ring.unlink()


# ------------------------------------------------------------ codecs
def test_wire_codec_roundtrips():
    sub = [(7, pickle.dumps((sum, ((1, 2),))), "alpha"),
           (2 ** 40, b"", "")]
    assert decode_submit_batch(encode_submit_batch(sub)) == sub
    done = [(7, 1.25, 2.5, DONE_OK, pickle.dumps(3)),
            (9, 0.0, 0.5, DONE_ERROR, "tb".encode())]
    assert decode_done_batch(encode_done_batch(done)) == done


# ------------------------------------------------------------ sim knobs
def test_sim_costs_ipc_knobs():
    costs = SimCosts(ipc_submit_us=5.0, ipc_done_us=3.0)
    ch = SimCharger(costs)
    ch.ipc_submit()
    ch.ipc_done()
    assert ch.now == pytest.approx(8.0)
    assert SimCosts().ipc_submit_us > 0
    assert SimCosts().ipc_done_us > 0
