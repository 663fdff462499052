"""Multi-tenant job-scope subsystem (core.scopes): the scope-isolation
oracle (two concurrent scopes running matmul + sparse-LU produce
byte-identical per-scope results and the same dependence orderings as
each run alone, across all four policies on BOTH drivers), per-scope
record-and-replay steady state (two tenants submitting structurally
identical graphs concurrently each replay with ZERO lock acquisitions
and ZERO mailbox messages per iteration, in the simulator AND on real
threads), the FairAdmission layer (weighted-deficit grants, shared
admission window, per-scope max_inflight backpressure), the region
keying shim, and the serve-engine satellites (per-engine request ids,
JobScope-backed client queues)."""
import threading

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (FairAdmission, RuntimeSimulator, ScopedRegion,
                        SimTaskSpec, TaskRuntime, scoped_deps)
from repro.core.engine import ReplayPolicy
from repro.core.sched.placement import RoundRobinPlacement
from repro.core.shards import stable_region_hash
from repro.core.taskgraph_apps import (run_matmul, run_sparselu,
                                       sim_app_specs, sparselu_oracle)
from repro.core.wd import DepMode, WorkDescriptor

IN, OUT, INOUT = DepMode.IN, DepMode.OUT, DepMode.INOUT

ALL_MODES = ("sync", "dast", "ddast", "sharded")


# ------------------------------------------------------------ helpers
def _relabel(specs, prefix):
    """Copy a spec graph with scope-distinct labels (recursing into
    nested children) so per-scope tasks are identifiable in the shared
    exec_order."""
    out = []
    for s in specs:
        out.append(SimTaskSpec(
            dur=s.dur, deps=s.deps,
            children=_relabel(s.children, prefix) if s.children else None,
            label=f"{prefix}.{s.label}"))
    return out


def _submission_events(specs):
    events = {}
    for s in specs:
        for region, m in s.deps:
            events.setdefault(region, []).append(
                (s.label, "w" if m.writes else "r"))
    return events


def _check_region_order(events, sub_events):
    """Writers executed in submission order; every read saw the
    sequentially-correct last writer (same oracle the engine tests use
    for solo runs — passing it means the scope's dependence ordering is
    exactly what it would be alone)."""
    for region, evs in events.items():
        sub = sub_events[region]
        writes = [l for l, k in evs if k == "w"]
        assert writes == [l for l, k in sub if k == "w"], (region, evs)
        seq_last = {}
        cur = None
        for l, k in sub:
            if k == "w":
                cur = l
            else:
                seq_last[l] = cur
        cur = None
        for l, k in evs:
            if k == "w":
                cur = l
            else:
                assert cur == seq_last[l], (region, evs)


def _check_scope_order(result, specs):
    labels = {s.label for s in specs}
    pos = {l: i for i, l in enumerate(result.exec_order) if l in labels}
    assert len(pos) == len(labels)
    sub = _submission_events(specs)
    events = {r: sorted(evs, key=lambda e: pos[e[0]])
              for r, evs in sub.items()}
    _check_region_order(events, sub)


_SOLO = {}


def _solo_refs():
    """Byte-exact single-tenant references, computed once (the kernels
    are deterministic, so any mode/driver gives the same bytes)."""
    if not _SOLO:
        rng = np.random.RandomState(7)
        a = rng.rand(16, 16).astype(np.float32)
        b = rng.rand(16, 16).astype(np.float32)
        n = 20
        m = rng.rand(n, n).astype(np.float32) + np.eye(n, dtype=np.float32) * n
        with TaskRuntime(num_workers=2, mode="sync") as rt:
            _SOLO["a"], _SOLO["b"], _SOLO["m"] = a, b, m
            _SOLO["mm"] = run_matmul(rt, a, b, bs=4)
            _SOLO["lu"] = run_sparselu(rt, m, bs=4)
    return _SOLO


# ------------------------------------------------------ keying shim
def test_scoped_deps_keying_shim():
    deps = [(("A", 0, 0), IN), (("C", 1), INOUT)]
    assert scoped_deps(None, deps) is deps          # identity: no scope
    wrapped = scoped_deps(3, deps)
    assert wrapped == ((ScopedRegion(3, ("A", 0, 0)), IN),
                       (ScopedRegion(3, ("C", 1)), INOUT))
    # two scopes touching the same app region produce distinct keys
    # (no false dependence possible) AND distinct shard hashes
    r1 = ScopedRegion(1, ("A", 0, 0))
    r2 = ScopedRegion(2, ("A", 0, 0))
    assert r1 != r2
    assert stable_region_hash(r1) != stable_region_hash(r2)


def test_wd_inherits_scope_from_parent():
    root = WorkDescriptor(func=None, label="r", scope=9)
    child = WorkDescriptor(func=None, label="c", parent=root)
    grand = WorkDescriptor(func=None, label="g", parent=child)
    assert child.scope == 9 and grand.scope == 9
    stranger = WorkDescriptor(func=None, label="s")
    assert stranger.scope is None


def test_scope_task_regions_are_scope_qualified():
    with TaskRuntime(num_workers=1, mode="sync", num_clients=1) as rt:
        sc = rt.open_scope("t")
        wd = sc.task(lambda: None, deps=[(("A",), "inout")])
        sc.taskwait()
        assert wd.deps[0][0] == ScopedRegion(sc.scope_id, ("A",))
        assert wd.scope == sc.scope_id


# ------------------------------------------------------ API contract
def test_open_scope_requires_clients():
    with TaskRuntime(num_workers=1, mode="sync") as rt:
        with pytest.raises(ValueError, match="num_clients"):
            rt.open_scope("nope")


def test_scope_parameter_validation():
    with TaskRuntime(num_workers=1, mode="sync", num_clients=1) as rt:
        with pytest.raises(ValueError):
            rt.open_scope("w", weight=0.0)
        with pytest.raises(ValueError):
            rt.open_scope("c", max_inflight=0)


def test_client_slot_exhaustion():
    with TaskRuntime(num_workers=1, mode="sync", num_clients=1) as rt:
        errs = []
        # both threads stay alive through both attempts: a dead client
        # thread's ident (and with it its slot) may be reused, which is
        # fine for SPSC safety but not what this test is about
        attempted = threading.Barrier(2)

        def client():
            try:
                rt.open_scope("x")
            except RuntimeError as e:
                errs.append(e)
            attempted.wait()

        ts = [threading.Thread(target=client) for _ in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert len(errs) == 1           # one slot, two LIVE clients


def test_client_slots_recycled_after_scope_close():
    """Tenant-session churn (thread per session) must be bounded by
    CONCURRENT clients, not total ones: a thread's submit slot returns
    to the pool when its last scope closes."""
    with TaskRuntime(num_workers=1, mode="sync", num_clients=1) as rt:
        for k in range(3):              # 3 sessions, 1 client slot
            def session(k=k):
                sc = rt.open_scope(f"s{k}")
                sc.task(_spin, deps=[((0,), "inout")])
                sc.close()

            t = threading.Thread(target=session)
            t.start()
            t.join()
        assert len(rt._free_client_slots) == 1


def test_run_scopes_validation():
    sim = RuntimeSimulator(2, "sync")
    with pytest.raises(ValueError):
        sim.run_scopes([])
    with pytest.raises(ValueError):
        sim.run_scopes([[SimTaskSpec(dur=1.0)]] * 3)    # 3 scopes, 2 cores
    with pytest.raises(ValueError):
        RuntimeSimulator(2, "dast").run_scopes(
            [[SimTaskSpec(dur=1.0)]] * 2)               # mgr core reserved
    with pytest.raises(ValueError):
        sim.run_scopes([[SimTaskSpec(dur=1.0)]], weights=[1.0, 2.0])


# ------------------------------------------- scope isolation oracle
@pytest.mark.parametrize("mode", ALL_MODES)
def test_sim_scope_isolation_oracle(mode):
    """Concurrent matmul + sparse-LU scopes: each scope's execution
    respects exactly the dependence ordering of its solo run, for every
    policy, and the rollups attribute every task to its scope."""
    mm = _relabel(sim_app_specs("matmul", 3), "mm")
    lu = _relabel(sim_app_specs("sparselu", 5), "lu")
    r = RuntimeSimulator(4, mode).run_scopes([mm, lu], names=["mm", "lu"])
    assert r.tasks == len(mm) + len(lu)
    assert r.scopes["mm"]["tasks"] == len(mm)
    assert r.scopes["lu"]["tasks"] == len(lu)
    _check_scope_order(r, mm)
    _check_scope_order(r, lu)


@pytest.mark.parametrize("mode", ALL_MODES)
def test_sim_scope_isolation_nested(mode):
    """A nested-task tenant (N-Body) next to a flat one."""
    nb = _relabel(sim_app_specs("nbody", 3), "nb")
    mm = _relabel(sim_app_specs("matmul", 3), "mm")
    r = RuntimeSimulator(4, mode).run_scopes([nb, mm], names=["nb", "mm"])
    assert r.tasks == r.scopes["nb"]["tasks"] + r.scopes["mm"]["tasks"]
    _check_scope_order(r, mm)
    _check_scope_order(r, nb)           # top-level timestep chain


@pytest.mark.parametrize("mode", ALL_MODES)
def test_threaded_scope_isolation_byte_identical(mode):
    """Two client threads, matmul + sparse-LU concurrently: per-scope
    results are byte-identical to each app run alone (per-scope
    dependence order fixes the float op order; the keying shim plus
    per-parent namespaces make cross-tenant interference impossible)."""
    refs = _solo_refs()
    outs = {}
    with TaskRuntime(num_workers=3, mode=mode, num_clients=2) as rt:
        def mm_client():
            with rt.open_scope("mm"):
                outs["mm"] = run_matmul(rt, refs["a"], refs["b"], bs=4)

        def lu_client():
            with rt.open_scope("lu"):
                outs["lu"] = run_sparselu(rt, refs["m"], bs=4)

        ts = [threading.Thread(target=mm_client),
              threading.Thread(target=lu_client)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    assert np.array_equal(outs["mm"], refs["mm"])
    assert np.array_equal(outs["lu"], refs["lu"])
    assert np.allclose(outs["lu"], sparselu_oracle(refs["m"], 4),
                       atol=2e-2)
    st = rt.stats.scopes
    assert st["mm"]["tasks"] == 4 ** 3
    assert st["lu"]["tasks"] > 0


# ------------------------------- per-scope replay: steady state
@pytest.mark.parametrize("mode", ALL_MODES)
def test_sim_two_scope_replay_steady_state(mode):
    """Acceptance: two scopes submitting structurally identical graphs
    concurrently each reach steady-state replay — iterations beyond the
    first add ZERO lock acquisitions and ZERO mailbox messages."""
    specs = [sim_app_specs("matmul", 3), sim_app_specs("matmul", 3)]
    r1 = RuntimeSimulator(6, mode, replay=True).run_scopes(
        specs, iterations=1)
    r4 = RuntimeSimulator(6, mode, replay=True).run_scopes(
        specs, iterations=4)
    assert r4.lock_acquisitions == r1.lock_acquisitions
    assert r4.messages == r1.messages
    for name in ("scope0", "scope1"):
        assert r4.scopes[name]["replay_iterations"] == 3
        assert r4.scopes[name]["tasks"] == 4 * 27


def _spin():
    x = 0.0
    for i in range(50):
        x += i * i
    return x


@pytest.mark.parametrize("mode", ALL_MODES)
def test_threaded_two_scope_replay_steady_state(mode):
    """Acceptance (real threads): after both tenants froze their
    recordings, further concurrent iterations perform zero graph-lock
    acquisitions and process zero mailbox messages."""
    iters, ntasks = 4, 30
    barrier = threading.Barrier(2)
    snap = []

    with TaskRuntime(num_workers=3, mode=mode, num_clients=2,
                     replay=True) as rt:
        def client(name):
            sc = rt.open_scope(name)
            for it in range(iters):
                for i in range(ntasks):
                    sc.task(_spin, deps=[((i % 7,), "inout")],
                            label=f"t{i}")
                sc.taskwait()
                barrier.wait()          # both tenants quiesced
                if name == "a" and it == 1:
                    st = rt.policy.stats()
                    snap.append((st["lock_acquisitions"],
                                 st["messages_processed"]))
                barrier.wait()
            sc.close()

        ts = [threading.Thread(target=client, args=(n,))
              for n in ("a", "b")]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        st = rt.policy.stats()
        final = (st["lock_acquisitions"], st["messages_processed"])
        assert final == snap[0], (mode, snap[0], final)
        for name in ("a", "b"):
            sc = next(s for s in rt._scopes if s.name == name)
            pol = rt.policy.scope_policy(sc.scope_id)
            assert pol.replay_iterations == iters - 1


def test_threaded_scope_divergence_is_isolated():
    """Tenant A diverging (different structure on iteration 2) must not
    disturb tenant B's steady-state replay."""
    count = {"a": 0, "b": 0}
    lock = threading.Lock()

    def bump(k):
        with lock:
            count[k] += 1

    with TaskRuntime(num_workers=2, mode="sync", num_clients=2,
                     replay=True) as rt:
        def client_a():
            sc = rt.open_scope("a")
            for it in range(4):
                if it == 1:             # structural divergence
                    for i in range(5):
                        sc.task(bump, "a", deps=[(("x", i), "inout")])
                else:
                    for i in range(8):
                        sc.task(bump, "a", deps=[((i % 3,), "inout")])
                sc.taskwait()
            sc.close()

        def client_b():
            sc = rt.open_scope("b")
            for _ in range(4):
                for i in range(8):
                    sc.task(bump, "b", deps=[((i % 3,), "inout")])
                sc.taskwait()
            sc.close()

        ts = [threading.Thread(target=client_a),
              threading.Thread(target=client_b)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        pol_a = rt.policy.scope_policy(rt._scopes[0].scope_id) \
            if rt._scopes[0].name == "a" else \
            rt.policy.scope_policy(rt._scopes[1].scope_id)
        pol_b = rt.policy.scope_policy(
            next(s.scope_id for s in rt._scopes if s.name == "b"))
        assert isinstance(pol_a, ReplayPolicy)
        assert pol_a.invalidations >= 1
        assert pol_b.invalidations == 0
        assert pol_b.replay_iterations == 3
    assert count == {"a": 8 + 5 + 8 + 8, "b": 32}


def test_scope_taskwait_not_blocked_by_other_tenant_backlog():
    """A tenant's taskwait gates on ITS OWN subtree: another tenant's
    un-flushed submit buffers (global pending > 0) must not delay it."""
    with TaskRuntime(num_workers=2, mode="sharded", num_shards=4,
                     batch_size=8, num_clients=2) as rt:
        release = threading.Event()
        parked = threading.Event()
        done = []

        def b_client():
            sb = rt.open_scope("b")
            for i in range(3):          # < batch_size: stays buffered
                sb.task(_spin, deps=[((i,), "inout")])
            parked.set()
            release.wait()              # holds its backlog un-flushed
            sb.close()

        def a_client():
            sa = rt.open_scope("a")
            sa.task(_spin, deps=[((0,), "inout")])
            sa.taskwait()               # must return despite B's backlog
            done.append(True)
            sa.close()

        tb = threading.Thread(target=b_client)
        tb.start()
        parked.wait()
        assert rt._pending_msgs() > 0   # B's buffer really is pending
        ta = threading.Thread(target=a_client)
        ta.start()
        ta.join(timeout=20)
        assert done, "scope A's taskwait blocked on scope B's backlog"
        release.set()
        tb.join()


def test_shutdown_drains_abandoned_scope_with_buffered_submits():
    """A client thread that submits (into its slot's batch buffer) and
    departs without taskwait must not wedge shutdown: scope-root
    taskwaits flush EVERY slot, so the orphaned buffer ships."""
    done = []

    def drive():
        with TaskRuntime(num_workers=2, mode="sharded", num_shards=4,
                         batch_size=8, num_clients=1) as rt:
            def rude_client():
                sc = rt.open_scope("rude")
                for i in range(2):      # < batch_size: stays buffered
                    sc.task(_spin, deps=[((i,), "inout")])
                # departs without taskwait/close

            t = threading.Thread(target=rude_client)
            t.start()
            t.join()
        done.append(rt.stats.tasks_executed)

    driver = threading.Thread(target=drive, daemon=True)
    driver.start()
    driver.join(timeout=30)
    assert done, "shutdown hung on the abandoned scope's buffer"
    assert done[0] == 2


# ----------------------------------------------- fair admission layer
def test_fair_admission_weighted_grants():
    """2:1 weights get 2:1 ± 25% of the execution prefix while both
    tenants are backlogged (the bench_scopes CI gate, in miniature)."""
    def flood(n, tag):
        return [SimTaskSpec(dur=100.0, deps=[((tag, i), INOUT)],
                            label=f"{tag}.{i}") for i in range(n)]

    r = RuntimeSimulator(4, "sync").run_scopes(
        [flood(90, "a"), flood(90, "b")], weights=[2.0, 1.0],
        names=["a", "b"])
    pre = r.exec_order[:90]             # both still backlogged here
    na = sum(1 for l in pre if l.startswith("a."))
    nb = len(pre) - na
    assert 1.5 <= na / nb <= 2.5, (na, nb)


def test_fair_admission_backpressure_cap():
    inner = RoundRobinPlacement(2)
    fa = FairAdmission(inner, window=100)
    fa.register_scope(1, weight=1.0, max_inflight=2)
    wds = [WorkDescriptor(func=None, label=f"t{i}", scope=1)
           for i in range(10)]
    for wd in wds:
        fa.push(wd)
    # at most max_inflight of the scope's tasks occupy the shared pool
    assert inner.ready_count() == 2
    assert fa.ready_count() == 10
    got = set()
    for _ in range(10):
        assert inner.ready_count() <= 2
        wd = fa.pop(0)
        assert wd is not None
        got.add(wd.label)
    assert fa.pop(0) is None
    assert got == {f"t{i}" for i in range(10)}
    adm = fa.scope_admission(1)
    assert adm["admitted"] == 10
    assert adm["admission_waits"] == 8  # tasks 3..10 each waited once
    assert adm["max_queued"] == 8       # ring high-water behind the cap


def test_fair_admission_window_backpressure():
    inner = RoundRobinPlacement(2)
    fa = FairAdmission(inner, window=3)
    fa.register_scope(1, weight=1.0)
    fa.register_scope(2, weight=1.0)
    for i in range(4):
        fa.push(WorkDescriptor(func=None, label=f"a{i}", scope=1))
        fa.push(WorkDescriptor(func=None, label=f"b{i}", scope=2))
    assert inner.ready_count() == 3     # shared window binds
    drained = 0
    while fa.pop(0) is not None:
        drained += 1
        assert inner.ready_count() <= 3
    assert drained == 8


def test_fair_admission_forwards_shard_rekey():
    """ShardedPolicy.resize re-keys a shard-affine placement through
    getattr(placement, 'set_num_shards') — the wrapper must not hide
    it."""
    from repro.core.sched.placement import ShardAffinePlacement
    inner = ShardAffinePlacement(2, num_shards=4)
    fa = FairAdmission(inner)
    fa.set_num_shards(8)
    assert inner._num_shards == 8


def test_fair_admission_default_context_bypasses_rings():
    inner = RoundRobinPlacement(2)
    fa = FairAdmission(inner, window=1)
    fa.register_scope(1, weight=1.0)
    wd = WorkDescriptor(func=None, label="root-task")   # scope None
    fa.push(wd)
    assert inner.ready_count() == 1     # straight through, no window
    assert fa.pop(0) is wd


# ------------------------------------------------- serve satellites
class _StubModel:
    """Just enough ModelAPI for the request layer: constant logits."""

    class cfg:                                 # noqa: N801 (ModelAPI.cfg)
        vocab_size = 16

    def init_cache(self, batch, max_len):
        return {}

    def decode_step(self, params, cache, tokens, pos):
        logits = jnp.zeros((tokens.shape[0], 16)).at[:, 7].set(1.0)
        return logits, cache


def test_serve_engines_number_requests_independently():
    from repro.serve.engine import Request, ServeEngine
    e1 = ServeEngine(_StubModel(), None, batch_slots=2, max_len=8,
                     num_clients=1)
    e2 = ServeEngine(_StubModel(), None, batch_slots=2, max_len=8,
                     num_clients=1)
    ids1 = [e1.submit(Request(prompt=[1], max_new_tokens=1)).req_id
            for _ in range(3)]
    ids2 = [e2.submit(Request(prompt=[1], max_new_tokens=1)).req_id
            for _ in range(3)]
    # a module-global counter would interleave these
    assert ids1 == [0, 1, 2]
    assert ids2 == [0, 1, 2]


def test_serve_engine_runtime_scopes():
    """Each client queue rides a JobScope on the real runtime: outputs
    unchanged, per-client fairness counters live in the scope layer."""
    from repro.serve.engine import Request, ServeEngine
    with TaskRuntime(num_workers=2, mode="ddast", num_clients=2) as rt:
        eng = ServeEngine(_StubModel(), None, batch_slots=2, max_len=8,
                          num_clients=2, runtime=rt,
                          client_weights=[2.0, 1.0])
        reqs = [eng.submit(Request(prompt=[1, 2], max_new_tokens=2),
                           i % 2) for i in range(6)]
        eng.run_until_drained()
        assert all(r.output == [7, 7] for r in reqs)
        adm = eng.scope_admission()
        assert adm["client0"]["admitted"] == 3
        assert adm["client1"]["admitted"] == 3
        assert adm["client0"]["weight"] == 2.0
    st = rt.stats.scopes
    assert st["client0"]["tasks"] == 3 and st["client1"]["tasks"] == 3


def test_serve_engine_stepped_from_dedicated_thread():
    """The serving thread differs from the constructing (main) thread:
    the pump must claim its own submit slot (one extra num_clients)
    rather than share the main slot's SPSC queue."""
    from repro.serve.engine import Request, ServeEngine
    with TaskRuntime(num_workers=2, mode="sharded", num_shards=4,
                     num_clients=3) as rt:
        eng = ServeEngine(_StubModel(), None, batch_slots=2, max_len=8,
                          num_clients=2, runtime=rt)
        reqs = [eng.submit(Request(prompt=[1], max_new_tokens=2), i % 2)
                for i in range(4)]
        server = threading.Thread(target=eng.run_until_drained)
        server.start()
        # the main thread keeps submitting default-context tasks
        # concurrently — distinct slots, so both streams survive
        for i in range(50):
            rt.task(_spin, deps=[((i % 5,), "inout")])
        rt.taskwait()
        server.join(timeout=30)
        assert not server.is_alive()
        assert all(r.output == [7, 7] for r in reqs)
