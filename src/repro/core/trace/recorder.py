"""Per-task event tracing: append-only ring buffers, one per worker slot.

The paper argues about *where time goes inside the runtime* — manager
queue residency, lock waits, idle drains — and "Detrimental task
execution patterns" (PAPERS.md, 2406.03077) shows per-task lifecycle
timelines are enough to detect the pathologies automatically. This
module is the recording layer both drivers share:

  * task lifecycle:  ``created`` → ``deps_resolved`` → ``ready`` →
    ``start`` → ``end`` (stamped by whichever layer owns the
    transition: driver, dependence policy, placement);
  * manager side:    ``msg_enqueued`` / ``msg_drained`` (per-worker
    queues and shard mailboxes), ``steal`` (a ready task left another
    slot's deque), ``admission_defer`` (FairAdmission held a tenant's
    task in its ring);
  * boundaries:      ``quiesce`` at every root-taskwait quiescence,
    carrying the replay iteration count so consumers can tell live
    windows (manager events present) from replayed ones (elided by
    design);
  * spans:           one ``span`` event per stretch of work, stamped at
    its start and carrying its end: ``manager`` (one manager session
    that processed messages, on the slot of the thread that ran it,
    payload = messages) from the dependence policy, and ``admit`` /
    ``dispatch`` / ``readback`` / ``track`` from the serving engine's
    step (``repro.serve.engine``);
  * counters:        per-slot tallies with no time, such as
    ``empty_poll`` (a manager session that found no message).

The task body's ``start`` → ``end`` brackets the host's call of the
body. For a JAX body that is the dispatch of its jitted call, not the
device work it queues: the device runs on after ``end``.

Design constraints, in order:

1. **No new locks on the hot path.** Each slot appends to its own
   ``collections.deque(maxlen=capacity)`` — append is GIL-atomic and
   O(1), and a bounded deque drops from the head, so a run that
   outlives the capacity loses the *oldest* events per slot and nothing
   blocks. Producers that act on behalf of no particular slot
   (dependence analysis, the dast manager thread, the sharded router) use
   one shared overflow ring; deque append atomicity makes that safe
   too.
2. **Disabled cost = one attribute check.** Every call site guards with
   ``if tracer.enabled:``; ``NULL_TRACER`` answers ``enabled = False``
   and no-ops everything, so ``trace=False`` runs never construct an
   event tuple. Recording can be switched off and on again on a live
   recorder by assigning ``enabled``; producers then skip as they do
   for ``NULL_TRACER``, so a consumer can record one stretch of a run.
3. **One schema for both drivers.** Events are plain tuples
   ``(t, ev, wd_id, slot, label, scope, data)``; the clock is a
   callable — by default ``time.perf_counter()`` less the recorder's
   ``origin`` under threads (``origin + t`` is the ``perf_counter``
   reading again, for consumers that set events against another host
   clock), ``SimCharger.now`` (virtual µs) under the simulator. The
   simulator additionally prices each stamp (``SimCosts.trace_event``)
   through the charger so the traced-vs-untraced overhead gate in
   ``bench_traces.py`` measures a real cost, not zero by construction.
"""
from __future__ import annotations

import json
import time
from collections import deque
from typing import (Any, Callable, Dict, List, NamedTuple, Optional, Tuple)

# -- event kinds (string constants so traces stay greppable) -----------
EV_CREATED = "created"            # WD allocated + submitted by a worker
EV_DEPS = "deps_resolved"         # dependence analysis applied (per
#                                   shard portion in sharded mode)
EV_READY = "ready"                # pushed into a slot's ready deque;
#                                   slot = target deque; data: "affine"
#                                   or ("band", b) when applicable
EV_START = "start"                # body started on slot
EV_END = "end"                    # body finished on slot
EV_MSG_ENQ = "msg_enqueued"       # Submit/Done posted to a queue/mailbox
EV_MSG_DRAIN = "msg_drained"      # a manager processed one entry
EV_DELEGATE = "delegated"         # Submit/Done portion published to a
#                                   shard's MPSC request list (the
#                                   delegation analogue of msg_enqueued;
#                                   same (kind, shard, n) payload)
EV_COMBINE = "combined"           # one combine session: the lock holder
#                                   applied n published portions in a
#                                   single combined critical section
EV_STEAL = "steal"                # popped from another slot's deque;
#                                   slot = thief, data = victim slot
EV_ADMIT_DEFER = "admission_defer"  # FairAdmission held the task back
EV_QUIESCE = "quiesce"            # root-taskwait quiescence boundary
EV_SPAN = "span"                  # a stretch of work on slot: t = its
#                                   start, label = its name, data =
#                                   (end, payload)

# -- span names and counters --------------------------------------------
SPAN_MANAGER = "manager"          # one manager session that processed
#                                   messages; payload: how many
SPAN_ADMIT = "admit"              # serving engine: admission, with the
#                                   slot-cache resets; payload: admitted
SPAN_PREFILL = "prefill"          # serving engine: a prompt chunk's
#                                   upload and launch; payload: its tokens
SPAN_DISPATCH = "dispatch"        # serving engine: uploads + step launch
SPAN_READBACK = "readback"        # serving engine: waiting for the
#                                   step's tokens on the host
SPAN_TRACK = "track"              # serving engine: the per-slot loop
COUNT_EMPTY_POLL = "empty_poll"   # manager sessions that found nothing

# -- fault-tolerance events (core.errors; process-backend supervisor
#    and the threaded retry path) ---------------------------------------
EV_WORKER_LOST = "worker_lost"    # a worker process died; data: pid,
#                                   exitcode, in-flight task labels
EV_RESPAWN = "respawn"            # supervisor replaced the worker;
#                                   slot = the respawned worker's slot
EV_RETRY = "retry"                # a task was re-dispatched after a
#                                   fault; data: attempt no. + reason
EV_TIMEOUT_KILL = "timeout_kill"  # per-task timeout expired: the stuck
#                                   worker was killed
EV_SCOPE_EXPIRED = "scope_expired"  # a scope's deadline/budget ran out;
#                                   its unrun tasks drain-and-fail
EV_TRACE_LOST = "trace_lost"      # a crashed worker's in-flight task
#                                   events could not be reconstructed

TASK_LIFECYCLE = (EV_CREATED, EV_DEPS, EV_READY, EV_START, EV_END)
FAULT_EVENTS = (EV_WORKER_LOST, EV_RESPAWN, EV_RETRY, EV_TIMEOUT_KILL,
                EV_SCOPE_EXPIRED, EV_TRACE_LOST)


class TraceEvent(NamedTuple):
    t: float                      # clock units (s threaded, µs sim)
    ev: str
    wd_id: int                    # -1 for manager/boundary events
    slot: int                     # acting slot; -1 when unattributed
    label: str
    scope: Optional[int]
    data: Any                     # event-specific payload (JSON-able)


def span_end(e: TraceEvent) -> float:
    """The end of a ``span`` event (its ``t`` is the start)."""
    return e.data[0]


class NullTraceRecorder:
    """The ``trace=False`` stub: every producer guards on ``.enabled``,
    so these bodies exist only for callers that skip the guard."""

    enabled = False
    origin = None

    def task_event(self, ev, wd, slot, data=None) -> None:
        pass

    def mgr_event(self, ev, slot, data=None) -> None:
        pass

    def span(self, name, slot, t0, data=None) -> float:
        return t0

    def count(self, name, slot, n=1) -> None:
        pass

    def counts(self) -> Dict[str, List[int]]:
        return {}

    def quiesce(self, data=None) -> None:
        pass

    def ingest(self, events) -> None:
        pass

    def events(self) -> List[TraceEvent]:
        return []

    @property
    def dropped(self) -> int:
        return 0

    @property
    def total_appended(self) -> int:
        return 0


NULL_TRACER = NullTraceRecorder()


class TraceRecorder:
    """Per-slot bounded ring buffers + merge/save. One instance per run.

    Without ``clock`` the recorder stamps ``perf_counter() - origin``;
    ``origin`` (default: the ``perf_counter`` reading at construction)
    may be moved before recording starts. With a ``clock`` of its own
    (the simulator's virtual time) ``origin`` is None."""

    enabled = True

    def __init__(self, num_slots: int,
                 clock: Optional[Callable[[], float]] = None,
                 capacity: int = 1 << 16, charge=None,
                 time_unit: str = "s",
                 origin: Optional[float] = None) -> None:
        if num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.num_slots = num_slots
        if clock is None:
            self.origin: Optional[float] = (
                time.perf_counter() if origin is None else origin)
            clock = self._since_origin
        else:
            self.origin = origin
        self.clock = clock
        self.capacity = capacity
        self.time_unit = time_unit          # "s" (threads) | "us" (sim)
        # priced stamps under the simulator; None under real threads
        self._charge = charge
        # rings[slot] for attributed producers, rings[-1] shared overflow
        self._rings: List[deque] = [deque(maxlen=capacity)
                                    for _ in range(num_slots + 1)]
        self._appended = [0] * (num_slots + 1)
        self._counts: Dict[str, List[int]] = {}

    def _since_origin(self) -> float:
        return time.perf_counter() - self.origin

    # -- producers (hot path: one append, no lock) ---------------------
    def _emit(self, slot: int, tup: Tuple) -> None:
        i = slot if 0 <= slot < self.num_slots else self.num_slots
        self._rings[i].append(tup)
        self._appended[i] += 1

    def task_event(self, ev: str, wd, slot: int, data=None) -> None:
        if self._charge is not None:
            self._charge.trace_event()
        self._emit(slot, (self.clock(), ev, wd.wd_id, slot, wd.label,
                          wd.scope, data))

    def mgr_event(self, ev: str, slot: int, data=None) -> None:
        if self._charge is not None:
            self._charge.trace_event()
        self._emit(slot, (self.clock(), ev, -1, slot, "", None, data))

    def quiesce(self, data=None) -> None:
        self.mgr_event(EV_QUIESCE, -1, data)

    def span(self, name: str, slot: int, t0: float, data=None) -> float:
        """Record the span ``name`` from ``t0`` (an earlier reading of
        ``clock``) to now on ``slot``; returns its end, which starts the
        next span of a sequence."""
        if self._charge is not None:
            self._charge.trace_event()
        t1 = self.clock()
        self._emit(slot, (t0, EV_SPAN, -1, slot, name, None, (t1, data)))
        return t1

    def count(self, name: str, slot: int, n: int = 1) -> None:
        """Add ``n`` to the per-slot counter ``name`` (no event)."""
        c = self._counts.get(name)
        if c is None:
            c = self._counts.setdefault(name, [0] * (self.num_slots + 1))
        c[slot if 0 <= slot < self.num_slots else self.num_slots] += n

    def ingest(self, events) -> None:
        """Merge pre-stamped tuples recorded in another process (the
        process backend's per-worker rings, shipped at shutdown, and its
        replay-plane start/end stamps). Tuples must already be in the
        standard 7-field schema on this recorder's clock; the slot is
        read from the tuple, so worker events land in their own rings
        and the usual overflow accounting applies."""
        for e in events:
            self._emit(e[3], tuple(e))

    # -- consumers (cold path) -----------------------------------------
    @property
    def dropped(self) -> int:
        """Events evicted by ring overflow (oldest-first, per slot)."""
        return sum(self._appended) - sum(len(r) for r in self._rings)

    @property
    def total_appended(self) -> int:
        """Lifetime append count — a cheap has-anything-new probe for
        periodic consumers (the tuner's quiescence hook)."""
        return sum(self._appended)

    def events(self) -> List[TraceEvent]:
        """All retained events, merged and time-sorted. The sort is
        stable, so same-timestamp events keep per-ring append order.
        Safe while producers run (a live sampler sweep reads them)."""
        evs = [TraceEvent(*e) for ring in self._rings
               for e in _snapshot(ring)]
        evs.sort(key=lambda e: e.t)
        return evs

    def counts(self) -> Dict[str, List[int]]:
        """Per-slot counters by name; the last entry is the overflow
        slot's (producers acting for no particular slot)."""
        return {k: list(v) for k, v in self._counts.items()}

    def save(self, path: str) -> None:
        save_trace(path, self.events(), time_unit=self.time_unit,
                   num_slots=self.num_slots, dropped=self.dropped,
                   origin=self.origin, counts=self.counts())


def _snapshot(ring: deque) -> tuple:
    """Copy a ring another thread may be appending to. ``tuple`` copies
    in C without giving up the GIL, so a producer can only slip in
    through a garbage collection mid-copy; the deque then raises, and
    the copy is taken again."""
    while True:
        try:
            return tuple(ring)
        except RuntimeError:        # deque mutated during iteration
            pass


def save_trace(path: str, events, time_unit: str = "s",
               num_slots: int = 0, dropped: int = 0,
               origin: Optional[float] = None,
               counts: Optional[Dict[str, List[int]]] = None) -> None:
    """Write an event list in :meth:`TraceRecorder.save` format — for
    results that carry merged events but no recorder (``SimResult``,
    a post-shutdown ``RuntimeStats``)."""
    if not num_slots:
        num_slots = max((e[3] for e in events), default=0) + 1
    with open(path, "w") as f:
        json.dump({"time_unit": time_unit,
                   "num_slots": num_slots,
                   "dropped": dropped,
                   "origin": origin,
                   "counts": counts or {},
                   "events": [list(e) for e in events]}, f)


_META = ("time_unit", "num_slots", "dropped", "origin", "counts")


def load_trace(path: str) -> Tuple[List[TraceEvent], dict]:
    """Load a :meth:`TraceRecorder.save` file. Tuple payloads round-trip
    as lists; consumers index ``data`` rather than type-check it."""
    with open(path) as f:
        doc = json.load(f)
    events = [TraceEvent(*e) for e in doc["events"]]
    meta = {k: doc.get(k) for k in _META}
    return events, meta


def replay_iterations_of(policy, scope_id=None) -> int:
    """The replay iteration count the ``quiesce`` event should carry:
    resolved through the scope multiplexer when one is present, 0 for
    policies with no replay wrapper. Shared by both drivers so the
    boundary payloads are identical."""
    resolve = getattr(policy, "scope_policy", None)
    if resolve is not None:
        policy = resolve(scope_id)      # None -> the default root slot
    return getattr(policy, "replay_iterations", 0)
