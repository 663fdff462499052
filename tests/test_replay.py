"""Record-and-replay subsystem (core.engine.replay): the replay-vs-live
oracle (identical dependence orderings and ready-order constraints for
all four wrapped policies on the three paper apps over >= 3 iterations,
with ZERO graph-lock acquisitions and ZERO mailbox messages on the
steady-state path), invalidation (changed dep mode / added task /
changed region / fewer tasks -> fall back to live analysis and
re-record), generation-counter latch reuse, plus the satellite features
that rode along: Done batching, shard-id affinity keying, and per-shard
stat carry across resize."""
import threading

import pytest

from repro.core import (DynamicTuner, RuntimeSimulator, TaskRuntime,
                        TunerConfig)
from repro.core.engine import (ReplayPolicy, ShardAffinePlacement,
                               make_placement, make_policy)
from repro.core.engine.replay import ReplayGraph
from repro.core.shards import stable_region_hash
from repro.core.taskgraph_apps import sim_app_specs
from repro.core.wd import DepMode, TaskState, WorkDescriptor

IN, OUT, INOUT = DepMode.IN, DepMode.OUT, DepMode.INOUT

ALL_MODES = ("sync", "dast", "ddast", "sharded")
APPS = [("matmul", 3), ("nbody", 3), ("sparselu", 5)]


# ------------------------------------------------------------ helpers
def _run_specs_threaded(rt, specs, log=None):
    """Execute a SimTaskSpec graph on the real runtime (recursing into
    nested children). With `log`, each task body records (label, r/w)
    events per region under a lock."""
    lock = threading.Lock()

    def body(spec):
        if log is not None:
            with lock:
                for region, m in spec.deps:
                    log.setdefault(region, []).append(
                        (spec.label, "w" if m.writes else "r"))
        if spec.children:
            for ch in spec.children:
                rt.task(body, ch, deps=ch.deps, label=ch.label)
            rt.taskwait()

    for s in specs:
        rt.task(body, s, deps=s.deps, label=s.label)
    rt.taskwait()


def _submission_events(specs):
    events = {}
    for s in specs:
        for region, m in s.deps:
            events.setdefault(region, []).append(
                (s.label, "w" if m.writes else "r"))
    return events


def _check_region_order(events, sub_events):
    """Writers executed in submission order; every read saw the
    sequentially-correct last writer."""
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


def _count_tasks(specs):
    n = 0
    stack = [list(specs)]
    while stack:
        for s in stack.pop():
            n += 1
            if s.children:
                stack.append(s.children)
    return n


def _lockmsg(policy):
    st = policy.stats()
    return st["lock_acquisitions"], st["messages_processed"]


# ------------------------------------------------- the acceptance oracle
@pytest.mark.parametrize("mode", ALL_MODES)
@pytest.mark.parametrize("app,scale", APPS)
def test_replay_matches_live_oracle(app, scale, mode):
    """>= 3 iterations of each paper app under every wrapped policy:
    every iteration respects the dependence ordering, and from iteration
    2 on the policy performs ZERO graph-lock acquisitions and ZERO
    mailbox messages (the issue's acceptance criterion)."""
    specs = sim_app_specs(app, scale)
    ntasks = _count_tasks(specs)
    with TaskRuntime(num_workers=2, mode=mode, num_shards=8,
                     replay=True) as rt:
        for it in range(3):
            log = {}
            _run_specs_threaded(rt, specs, log=log)
            if app != "nbody":          # flat graphs: full ordering check
                _check_region_order(log, _submission_events(specs))
            if it == 0:
                base = _lockmsg(rt.policy)
        assert _lockmsg(rt.policy) == base, \
            "steady-state replay touched locks or mailboxes"
        rep = rt.policy.stats()["replay"]
        assert rep["state"] == "replaying"
        assert rep["replay_iterations"] == 2
        assert rep["invalidations"] == 0
        assert rep["recorded_tasks"] == ntasks
    assert rt.stats.tasks_executed == 3 * ntasks
    assert rt.stats.replay_iterations == 2
    assert rt.stats.replayed_tasks == 2 * ntasks


@pytest.mark.parametrize("mode", ALL_MODES)
def test_runtime_stats_show_zero_cost_steady_state(mode):
    """RuntimeStats-level acceptance: a 3-iteration replay run ends with
    exactly the lock acquisitions and messages it had after its live
    first iteration — the two replayed iterations add zero of either.
    Both readings come from one run: under combining, the live count
    itself depends on thread timing, so two runs need not agree."""
    specs = sim_app_specs("sparselu", 5)
    with TaskRuntime(num_workers=2, mode=mode, num_shards=4,
                     replay=True) as rt:
        _run_specs_threaded(rt, specs)
        once_tasks = rt.stats.tasks_executed
        once = _lockmsg(rt.policy)
        assert once[0] > 0
        for _ in range(2):
            _run_specs_threaded(rt, specs)
        assert _lockmsg(rt.policy) == once
    thrice = rt.stats
    assert thrice.tasks_executed == 3 * once_tasks
    assert (thrice.lock_acquisitions, thrice.messages_processed) == once
    assert thrice.replay_iterations == 2
    assert thrice.replayed_tasks == 2 * once_tasks


@pytest.mark.parametrize("mode", ALL_MODES)
def test_sim_replay_matches_live(mode):
    """Simulated driver: replay over 3 iterations executes the same
    tasks, pays the live protocol exactly once (iteration 1), and its
    steady-state iterations cost 0 lock acquisitions / 0 messages and
    less virtual time than live iterations."""
    specs = sim_app_specs("matmul", 4)
    kw = dict(num_shards=8)
    live = RuntimeSimulator(4, mode, **kw).run(specs, iterations=3)
    rep = RuntimeSimulator(4, mode, replay=True, **kw).run(
        specs, iterations=3)
    once = RuntimeSimulator(4, mode, **kw).run(specs)
    assert rep.tasks == live.tasks == 3 * once.tasks
    assert rep.messages == once.messages
    assert rep.iter_lock_acq[1:] == [0, 0]
    assert rep.iter_messages[1:] == [0, 0]
    # exec order of every replay iteration respects the region protocol
    per_iter = len(rep.exec_order) // 3
    sub = _submission_events(specs)
    for it in range(3):
        order = rep.exec_order[it * per_iter:(it + 1) * per_iter]
        pos = {label: i for i, label in enumerate(order)}
        evs = {r: sorted(e, key=lambda x: pos[x[0]])
               for r, e in sub.items()}
        _check_region_order(evs, sub)
    # the win: steady-state replay iterations are faster than live ones
    assert min(rep.iter_makespans_us[1:]) < min(live.iter_makespans_us[1:])


def test_sim_replay_nested_nbody():
    specs = sim_app_specs("nbody", 4)   # nested timestep parents
    live = RuntimeSimulator(4, "ddast").run(specs, iterations=3)
    rep = RuntimeSimulator(4, "ddast", replay=True).run(specs, iterations=3)
    assert rep.tasks == live.tasks
    assert rep.iter_lock_acq[1:] == [0, 0]
    assert rep.iter_messages[1:] == [0, 0]


# ------------------------------------------------------- invalidation
def _iteration(rt, out, n, regions, mode=INOUT, tag=0):
    for i in range(n):
        rt.task(out.append, (tag, i), deps=[((i % regions,), mode)])
    rt.taskwait()


@pytest.mark.parametrize("mutate", ["mode", "region", "added"])
def test_invalidation_falls_back_and_rerecords(mutate):
    """A structural divergence (changed dep mode, changed region, added
    task) falls back to live analysis and re-records the new structure —
    which then replays lock- and message-free again. A divergence on the
    FIRST submission (the changed-mode case: task 0's key differs)
    re-records in the SAME iteration (nothing was replayed yet); a
    mid-iteration divergence finishes the replayed prefix under replay,
    live-analyzes the suffix, and re-records on the next iteration."""
    first_task_diverges = mutate == "mode"
    with TaskRuntime(num_workers=2, mode="sharded", num_shards=4,
                     replay=True) as rt:
        out = []

        def iter_a():
            _iteration(rt, out, 16, regions=4)

        def iter_b():
            if mutate == "mode":
                _iteration(rt, out, 16, regions=4, mode=IN, tag=1)
            elif mutate == "region":
                _iteration(rt, out, 16, regions=5, tag=1)
            else:
                _iteration(rt, out, 17, regions=4, tag=1)

        iter_a()                            # record
        iter_a()                            # replay
        assert rt.policy.stats()["replay"]["replay_iterations"] == 1
        iter_b()                            # diverge
        rep = rt.policy.stats()["replay"]
        assert rep["invalidations"] == 1
        if first_task_diverges:
            # redispatched to RECORDING before anything replayed: the
            # new structure froze at this very iteration's quiescence
            assert rep["state"] == "replaying"
            assert rep["recordings"] == 2
        else:
            assert rep["state"] == "recording"
            iter_b()                        # re-record the new structure
        base = _lockmsg(rt.policy)
        iter_b()                            # replay the new structure
        assert _lockmsg(rt.policy) == base
        rep = rt.policy.stats()["replay"]
        assert rep["state"] == "replaying"
        assert rep["recordings"] == 2
        # the old structure was retired into the cache, not dropped
        assert rep["cached_recordings"] == 2
    expected = 16 * 2 + (17 if mutate == "added" else 16) * \
        (2 if first_task_diverges else 3)
    assert rt.stats.tasks_executed == expected
    assert rt.stats.replay_invalidations == 1


def test_fallback_preserves_dependence_order():
    """The diverging suffix must still respect dependences against the
    replayed prefix: a suffix chain on a prefix region only runs after
    all replayed predecessors completed (they have: fallback buffers per
    namespace until the replayed siblings drain)."""
    with TaskRuntime(num_workers=3, mode="sync", replay=True) as rt:
        out = []

        def record_iter(extra):
            for i in range(12):
                rt.task(out.append, i, deps=[(("r", i % 3), INOUT)])
            if extra:                   # divergence: 6 extra chained tasks
                for i in range(12, 18):
                    rt.task(out.append, i, deps=[(("r", i % 3), INOUT)])
            rt.taskwait()

        record_iter(False)
        out.clear()
        record_iter(True)               # replays 12, falls back for 6
        # per-region submission order must hold across the replay/live seam
        by_region = {}
        for v in out:
            by_region.setdefault(v % 3, []).append(v)
        for r, vals in by_region.items():
            assert vals == sorted(vals), (r, vals)
    assert rt.stats.tasks_executed == 12 + 18


def test_fewer_tasks_iteration_is_correct_then_invalidates():
    """An iteration submitting a strict prefix of the recording executes
    correctly (two-phase latches keep never-submitted tasks unready) and
    invalidates at its quiescence."""
    with TaskRuntime(num_workers=2, mode="ddast", replay=True) as rt:
        out = []
        _iteration(rt, out, 10, regions=3)
        _iteration(rt, out, 10, regions=3)
        assert rt.policy.stats()["replay"]["state"] == "replaying"
        _iteration(rt, out, 6, regions=3)   # prefix only
        rep = rt.policy.stats()["replay"]
        assert rep["state"] == "recording"
        assert rep["invalidations"] == 1
        _iteration(rt, out, 6, regions=3)   # re-record
        _iteration(rt, out, 6, regions=3)   # replay
        assert rt.policy.stats()["replay"]["state"] == "replaying"
    assert rt.stats.tasks_executed == 10 * 2 + 6 * 3


def test_nested_divergence_in_child_namespace():
    """Divergence inside a nested parent's namespace (different children
    on iteration 2) while sibling namespaces replay."""
    with TaskRuntime(num_workers=2, mode="sharded", num_shards=4,
                     replay=True) as rt:
        out = []

        def parent_body(n, tag):
            for i in range(n):
                rt.task(out.append, (tag, i), deps=[((tag, i % 2), INOUT)])
            rt.taskwait()

        def iteration(n_b):
            rt.task(parent_body, 4, "a", deps=[(("pa",), INOUT)])
            rt.task(parent_body, n_b, "b", deps=[(("pb",), INOUT)])
            rt.taskwait()

        iteration(4)                    # record: both parents 4 children
        iteration(4)                    # replay
        iteration(6)                    # parent b diverges at child 5
        assert rt.policy.stats()["replay"]["invalidations"] == 1
        iteration(6)
        iteration(6)
        assert rt.policy.stats()["replay"]["state"] == "replaying"
    assert rt.stats.tasks_executed == 2 * (2 + 8) + 3 * (2 + 10)


# ------------------------------------------- generation-counter reuse
def test_generation_counter_latch_reuse_stress():
    """Many replay iterations must reuse the SAME frozen graph and
    latches (reset via the generation counter, not re-allocation) and
    stay lock- and message-free throughout."""
    iters = 30
    with TaskRuntime(num_workers=3, mode="sharded", num_shards=4,
                     replay=True) as rt:
        out = []
        _iteration(rt, out, 24, regions=6)
        graph0 = rt.policy.replay_graph
        latch0 = graph0.latches[0]
        base = _lockmsg(rt.policy)
        for _ in range(iters - 1):
            _iteration(rt, out, 24, regions=6)
            assert rt.policy.replay_graph is graph0
            assert rt.policy.replay_graph.latches[0] is latch0
        assert _lockmsg(rt.policy) == base
        assert rt.policy.stats()["replay"]["replay_iterations"] == iters - 1
    assert rt.stats.tasks_executed == 24 * iters
    # every iteration's per-region order was correct (4 entries per
    # region per iteration, in submission order within the iteration)
    by_region = {}
    for tag, i in out:
        by_region.setdefault(i % 6, []).append(i)
    for r, vals in by_region.items():
        assert len(vals) == 4 * iters
        for it in range(iters):
            chunk = vals[it * 4:(it + 1) * 4]
            assert chunk == sorted(chunk), (r, it, chunk)


def test_replay_graph_freeze_matches_depgraph_semantics():
    """Freeze-time analysis uses the shared RAW/WAW/WAR helper: chain +
    diamond resolve to the same edges a live DependenceGraph computes."""
    # namespace -1 (root): w(a) -> r(a) x2 -> w(a)  (diamond via WAR+RAW)
    kids = [
        ((("a",), OUT),),               # sid 0: writer
        ((("a",), IN),),                # sid 1: reader (RAW on 0)
        ((("a",), IN),),                # sid 2: reader (RAW on 0)
        ((("a",), INOUT),),             # sid 3: WAW on 0 + WAR on 1,2
    ]
    children = {-1: [(k, i) for i, k in enumerate(kids)]}
    g = ReplayGraph(children, [-1, -1, -1, -1], set())
    assert g.preds == [0, 1, 1, 3]
    assert sorted(g.succs[0]) == [1, 2, 3]
    assert g.succs[1] == [3] and g.succs[2] == [3]
    assert g.total_edges == 5
    assert [l.init for l in g.latches] == [1, 2, 2, 4]


def test_make_policy_replay_registry():
    pol = make_policy("replay:sharded", 3, num_shards=4)
    assert isinstance(pol, ReplayPolicy)
    assert pol.name == "replay(sharded)"
    assert pol.num_shards == 4          # delegation to the wrapped policy
    pol2 = make_policy("ddast", 3, replay=True)
    assert isinstance(pol2, ReplayPolicy)
    assert make_policy("sync", 3).__class__.__name__ == "SyncPolicy"
    with pytest.raises(ValueError):
        make_policy("replay:nope", 3)


# -------------------------------------------------- tuner interaction
def test_tuner_does_not_resize_while_recording_live():
    rt = TaskRuntime(num_workers=2, mode="sharded", num_shards=4,
                     replay=True)
    tuner = DynamicTuner(rt, TunerConfig(interval_s=0.0,
                                         shard_min_messages=1))
    pol = rt.policy
    # mid-recording: submit and fully drain so pending/in_graph are 0,
    # but the iteration (and with it the recording) is still open
    for i in range(8):
        wd = WorkDescriptor(func=None, deps=(((i % 2,), INOUT),),
                            parent=rt._root)
        pol.submit(wd, rt.num_workers)
    while True:
        pol.drain_all()
        wd = rt.placement.pop(rt.num_workers)
        if wd is None:
            if not pol.pending() and not pol.in_graph():
                break
            continue
        wd.mark_finished()
        pol.complete(wd, rt.num_workers)
    assert pol.recording_live
    before = pol.num_shards
    tuner.quiescent_callback(0)
    assert pol.num_shards == before     # guarded: no resize, no sample
    assert tuner._shard_prev_metric is None
    pol.notify_quiescent(True)          # freeze
    assert not pol.recording_live
    assert pol.replay_state == "replaying"


def test_tuner_with_replay_end_to_end():
    """Tuner + replay coexist: replay steady state generates no new
    messages, so the shard hill-climb simply starves (no spurious
    resizes), and correctness holds."""
    with TaskRuntime(num_workers=2, mode="sharded", num_shards=4,
                     replay=True) as rt:
        DynamicTuner(rt, TunerConfig(interval_s=0.0, shard_min_messages=8))
        out = []
        for _ in range(4):
            _iteration(rt, out, 20, regions=5)
        assert rt.policy.stats()["replay"]["replay_iterations"] == 3
    assert rt.stats.tasks_executed == 80


# ---------------------------------------------------- Done batching
def test_done_batch_single_mailbox_entry():
    """5 independent completions on one shard, batched: ONE
    DoneBatchMessage entry, latch arithmetic balances, graph empties."""
    pol = make_policy("sharded", 2, num_shards=1, batch_size=8)
    root = WorkDescriptor(func=None, label="root")
    wds = [WorkDescriptor(func=None, deps=(((("r", i)), INOUT),),
                          parent=root) for i in range(5)]
    for wd in wds:
        pol.submit(wd, 0)
    pol.flush(0)
    pol.drain_all()
    assert pol.stats()["messages_processed"] == 1   # one submit batch
    assert all(wd.state == TaskState.READY for wd in wds)
    for wd in wds:                      # all 5 Dones buffered, no flush
        wd.mark_finished()
        pol.complete(wd, 0)
    assert pol.stats()["messages_processed"] == 1
    pol.flush(0)
    pol.drain_all()
    assert all(wd.state == TaskState.COMPLETED for wd in wds)
    assert pol.in_graph() == 0
    # 1 submit batch + 1 done batch (5 dones shipped as one entry)
    assert pol.stats()["messages_processed"] == 2


def test_done_batching_reduces_sim_messages():
    specs = sim_app_specs("matmul", 4)
    unb = RuntimeSimulator(4, "sharded", num_shards=16).run(specs)
    bat = RuntimeSimulator(4, "sharded", num_shards=16,
                           batch_size=8).run(specs)
    assert bat.tasks == unb.tasks
    # Both sides batch: total entries must undercut unbatched by more
    # than the submit side alone ever could (the unbatched done side is
    # half the 360-entry total; submit-only batching therefore bottoms
    # out at > 180). The exact count is bounded below by distinct
    # shards-per-flush, so assert against that structural floor.
    assert bat.messages < unb.messages - unb.messages // 4


def test_done_batching_threaded_order_and_liveness():
    with TaskRuntime(num_workers=3, mode="sharded", num_shards=8,
                     batch_size=4) as rt:
        out = []
        for i in range(300):
            rt.task(out.append, i, deps=[((i % 11,), INOUT)])
        rt.taskwait()
    assert rt.stats.tasks_executed == 300
    by_region = {}
    for v in out:
        by_region.setdefault(v % 11, []).append(v)
    for r, vals in by_region.items():
        assert vals == sorted(vals), (r, vals[:8])


def test_pending_counts_done_buffers():
    pol = make_policy("sharded", 2, num_shards=2, batch_size=16)
    root = WorkDescriptor(func=None, label="root")
    wd = WorkDescriptor(func=None, deps=((("r",), INOUT),), parent=root)
    pol.submit(wd, 0)
    pol.flush(0)
    pol.drain_all()
    wd.mark_finished()
    pol.complete(wd, 0)                 # buffered Done
    assert pol.pending() == 1
    pol.flush(0)
    pol.drain_all()
    assert pol.pending() == 0
    assert wd.state == TaskState.COMPLETED


# ------------------------------------------- shard-id affinity keying
def test_affinity_keyed_by_shard_id():
    p = ShardAffinePlacement(3, num_shards=4)
    shard = stable_region_hash(("x", 0)) % 4
    # a DIFFERENT region on the same shard inherits the affinity
    other = next((("x", i) for i in range(1, 64)
                  if stable_region_hash(("x", i)) % 4 == shard))
    p.note_executed(WorkDescriptor(func=None, deps=(((("x", 0)), IN),)), 2)
    wd = WorkDescriptor(func=None, deps=((other, IN),))
    assert p.preferred_slot(wd) == 2
    # map is hard-bounded by the shard count on region churn
    for i in range(1000):
        p.note_executed(
            WorkDescriptor(func=None, deps=(((("r", i)), IN),)), i % 3)
    assert len(p._affinity) <= 4


def test_make_placement_passes_num_shards():
    p = make_placement("shard_affine", 3, num_shards=8)
    assert p._num_shards == 8
    p2 = make_placement("shard_affine", 3)
    assert p2._num_shards is None       # exact-region keying preserved
    assert make_placement("round_robin", 3, num_shards=8) is not None


def test_shard_keying_only_for_shard_backed_modes():
    """Only shard-partitioned policies switch affinity to shard-id
    keying; sync/dast/ddast keep the documented exact-region keying."""
    rt = TaskRuntime(num_workers=4, mode="ddast",
                     placement="shard_affine")
    assert rt.placement._num_shards is None
    rt2 = TaskRuntime(num_workers=4, mode="sharded", num_shards=8,
                      placement="shard_affine")
    assert rt2.placement._num_shards == 8


def test_resize_rekeys_shard_affinity():
    """ShardedPolicy.resize retunes the affinity partition function so
    placement keys keep matching the graph's shard assignment."""
    rt = TaskRuntime(num_workers=2, mode="sharded", num_shards=4,
                     placement="shard_affine")
    pl, pol = rt.placement, rt.policy
    pl.note_executed(WorkDescriptor(func=None, deps=((("q",), IN),)), 1)
    assert pl._num_shards == 4 and len(pl._affinity) == 1
    assert pol.resize(8)
    assert pl._num_shards == 8
    assert len(pl._affinity) == 0       # stale buckets dropped
    # exact-region placements are NOT converted by a resize
    direct = ShardAffinePlacement(3)
    direct.set_num_shards(8)
    assert direct._num_shards is None


# ------------------------------- multi-iteration paper apps (numeric)
def test_run_matmul_epochs_replay_numeric():
    import numpy as np
    from repro.core.taskgraph_apps import run_matmul_epochs
    a = np.random.RandomState(7).rand(48, 48).astype(np.float32)
    with TaskRuntime(num_workers=3, mode="sharded", num_shards=4,
                     replay=True) as rt:
        c = run_matmul_epochs(rt, a, a, bs=16, epochs=3)
        base = _lockmsg(rt.policy)
        # a fresh call (new C blocks, new closures, SAME structure)
        # keeps replaying: zero protocol cost for both extra epochs
        c2 = run_matmul_epochs(rt, a, a, bs=16, epochs=2)
        assert _lockmsg(rt.policy) == base
    np.testing.assert_allclose(c, 3 * (a @ a), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(c2, 2 * (a @ a), rtol=1e-3, atol=1e-3)
    assert rt.stats.replay_iterations == 4
    assert rt.stats.replay_invalidations == 0


def test_run_sparselu_epochs_replay_numeric():
    import numpy as np
    from repro.core.taskgraph_apps import (run_sparselu_epochs,
                                           sparselu_oracle)
    rng = np.random.RandomState(11)
    mats = [(rng.rand(48, 48).astype(np.float32)
             + 48 * np.eye(48, dtype=np.float32)) for _ in range(3)]
    with TaskRuntime(num_workers=3, mode="ddast", replay=True) as rt:
        outs = run_sparselu_epochs(rt, mats, bs=16)
    for m, out in zip(mats, outs):
        np.testing.assert_allclose(out, sparselu_oracle(m, 16),
                                   rtol=2e-3, atol=2e-3)
    assert rt.stats.replay_iterations == 2      # epochs 2 and 3 replayed
    assert rt.stats.replay_invalidations == 0


def test_run_nbody_epochs_replay_numeric():
    import numpy as np
    from repro.core.taskgraph_apps import nbody_oracle, run_nbody_epochs
    rng = np.random.RandomState(5)
    n, bs, steps = 32, 8, 4
    pos = rng.rand(n, 3).astype(np.float32)
    vel = np.zeros((n, 3), dtype=np.float32)
    mass = rng.rand(n).astype(np.float32)
    with TaskRuntime(num_workers=3, mode="sharded", num_shards=4,
                     replay=True) as rt:
        p, v = run_nbody_epochs(rt, pos, vel, mass, bs, timesteps=steps)
    po, vo = nbody_oracle(pos, vel, mass, steps)
    np.testing.assert_allclose(p, po, rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(v, vo, rtol=1e-3, atol=1e-4)
    # nested epochs: each timestep after the first replays
    assert rt.stats.replay_iterations == steps - 1
    assert rt.stats.replay_invalidations == 0


# ------------------------------------- resize carries per-shard stats
def test_resize_carries_per_shard_counters():
    pol = make_policy("sharded", 2, num_shards=4)
    root = WorkDescriptor(func=None, label="root")
    wds = [WorkDescriptor(func=None, deps=(((i,), INOUT),), parent=root)
           for i in range(12)]
    for wd in wds:
        pol.submit(wd, 0)
    pol.drain_all()
    for wd in wds:
        wd.mark_finished()
        pol.complete(wd, 0)
    pol.drain_all()
    st0 = pol.stats()
    msgs0 = st0["shard_messages"]
    assert sum(msgs0) == st0["messages_processed"] > 0
    assert pol.resize(8)
    st1 = pol.stats()
    # the per-shard history survived the swap (padded to the new width)
    assert sum(st1["shard_messages"]) == sum(msgs0)
    assert len(st1["shard_messages"]) == 8
    assert st1["messages_processed"] == st0["messages_processed"]
    # and keeps accumulating after the resize
    wd = WorkDescriptor(func=None, deps=((("z",), INOUT),), parent=root)
    pol.submit(wd, 0)
    pol.drain_all()
    st2 = pol.stats()
    assert sum(st2["shard_messages"]) == sum(msgs0) + 1
