"""Continuous-batching serving engine with the paper's asynchronous
organization at the request layer.

Clients NEVER touch the engine's scheduling structures (the paper's "no
direct mutation" rule): `submit()` pushes a request message into the
calling client's own SPSC queue (core.queues). The engine loop plays the
DDAST manager: it drains client queues — round-robin, up to
MAX_OPS_THREAD per client, stopping early once MIN_READY (free-slot fill)
is reached — admits requests into batch slots, and every engine step
advances ALL decoding slots by one token with a single batched
`decode_step`. Slots free as requests finish => true continuous batching
with per-slot positions.

Prompts reach the cache one of two ways. Where the model has
``prefill_chunk`` (attention mixers, dense FFNs), an admitted slot joins
a FIFO of prompts awaiting prefill, and each step first runs the FIFO
head's next chunk of ``PREFILL_CHUNK`` tokens: one jitted call, the
cache donated, that writes the chunk's K/V into the slot's cache rows.
The slot's decode lane is ignored until its prompt is in the cache; the
last chunk's greedy token is its first output, read back with that
step's decode tokens, and the slot decodes from the next step on.
Otherwise (recurrent mixers, MoE FFNs, models without the function) the
prompt is teacher-forced through the decode step, a token a step, after
the slot's cache lanes are zeroed.

With ``runtime=`` (a multi-tenant ``TaskRuntime(num_clients>=1)``) each
client queue becomes a :class:`~repro.core.scopes.JobScope` on the REAL
runtime instead of the engine's private drain loop: every drained
request is submitted as a scope task chained per client (region
``("reqchain",)`` INOUT under the scope's namespace — client FIFO for
free), the scopes' weighted-fair admission layer decides which client's
requests reach the admission buffer first, and per-client
``max_inflight`` backpressure bounds a flooding client's presence in
the shared pool. Request ids are per-engine (stamped at submit), so two
engines number their requests independently.

With ``trace=True`` the engine owns a one-slot
:class:`~repro.core.trace.TraceRecorder` (``engine.tracer``) and each
step records its spans on it: ``admit`` (draining and admission, the
slot-cache resets included; payload: requests admitted), ``prefill``
(on steps that run a chunk: its upload and launch; payload: the chunk's
prompt tokens), ``dispatch`` (the token and position uploads and the
decode step's launch), ``readback`` (the host waiting for the step's
tokens) and ``track`` (the per-slot loop). Switch
``engine.tracer.enabled`` to record one stretch of a run.
"""
from __future__ import annotations

import itertools
import threading
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.ddast import DDASTParams
from ..core.metrics import LogHistogram, prometheus_text
from ..core.queues import WorkerQueues
from ..core.sched import DagNode, bottom_levels, build_arrays
from ..core.trace import (NULL_TRACER, SPAN_ADMIT, SPAN_DISPATCH,
                          SPAN_PREFILL, SPAN_READBACK, SPAN_TRACK,
                          TraceRecorder)
from ..models.registry import ModelAPI

# prompt tokens one prefill call takes (fewer where max_len is shorter).
# On one TPU v5e (qwen2-0.5b, 128 slots x 1024) a 512-token chunk costs
# ~4 ms beside a ~36 ms decode step; 256 needs more chunks, so more steps,
# before a long prompt's first token, and 128 overruns one chunk a step.
PREFILL_CHUNK = 512


def prefill_program(model: ModelAPI):
    """The engine's chunk prefill, jitted with the cache donated, or None
    where the model has no ``prefill_chunk``. Its device program is
    ``jit_prefill_chunk``, apart from the decode step's
    ``jit_serve_step``."""
    chunk_fn = getattr(model, "prefill_chunk", None)
    if chunk_fn is None:
        return None

    def prefill_chunk(params, cache, tokens, slot, start, n_valid):
        return chunk_fn(params, cache, tokens, slot, start, n_valid)
    return jax.jit(prefill_chunk, donate_argnums=(1,))


@dataclass
class Request:
    prompt: List[int]
    max_new_tokens: int = 16
    # stamped by the owning engine at submit time (per-engine counter —
    # a module-global here would leak numbering across engines/tests)
    req_id: Optional[int] = None
    # stamped at submit: which client queue carried this request (the
    # per-tenant latency histogram's key; -1 = never submitted)
    client_id: int = -1
    output: List[int] = field(default_factory=list)
    done_event: threading.Event = field(default_factory=threading.Event)
    admitted_step: int = -1
    finished_step: int = -1


@dataclass
class _Slot:
    req: Optional[Request] = None
    pos: int = 0                    # next cache position
    prompt_left: int = 0            # prompt tokens not yet in the cache

    @property
    def free(self) -> bool:
        return self.req is None


class ServeEngine:
    def __init__(self, model: ModelAPI, params: Any, *, batch_slots: int = 4,
                 max_len: int = 256, num_clients: int = 4,
                 ddast: Optional[DDASTParams] = None, eos_id: int = -1,
                 runtime: Any = None,
                 client_weights: Optional[Sequence[float]] = None,
                 client_max_inflight: Optional[Sequence[Optional[int]]]
                 = None,
                 client_deadlines: Optional[Sequence[Optional[float]]]
                 = None, trace: bool = False):
        self.model = model
        self.params = params
        self.tracer = TraceRecorder(1) if trace else NULL_TRACER
        self.B = batch_slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.ddast = ddast or DDASTParams()
        self.client_queues = [WorkerQueues(i) for i in range(num_clients)]
        self._req_ids = itertools.count()
        # runtime-backed request layer: one JobScope per client queue
        self.runtime = runtime
        self._scopes: List[Any] = []
        self._admitq: deque = deque()   # GIL-atomic: filled by scope
        #   task bodies on worker threads, drained by the engine step
        if runtime is not None:
            ws = (list(client_weights) if client_weights is not None
                  else [1.0] * num_clients)
            caps = (list(client_max_inflight)
                    if client_max_inflight is not None
                    else [None] * num_clients)
            dls = (list(client_deadlines)
                   if client_deadlines is not None
                   else [None] * num_clients)
            if len(ws) != num_clients or len(caps) != num_clients \
                    or len(dls) != num_clients:
                raise ValueError("client_weights/client_max_inflight/"
                                 "client_deadlines must have "
                                 "num_clients entries")
            for c in range(num_clients):
                # deadline= makes the client scope an SLO tenant: the
                # scope records per-task met/missed + slack (exported
                # by metrics_snapshot), and hard-expires past the wall
                # deadline — tenant SLOs are wall-time promises here
                self._scopes.append(runtime.open_scope(
                    f"client{c}", weight=ws[c], max_inflight=caps[c],
                    deadline=dls[c]))
        self.slots = [_Slot() for _ in range(self.B)]
        self.cache = model.init_cache(self.B, max_len)
        self._tokens = np.zeros((self.B,), np.int32)
        self._pos = np.zeros((self.B,), np.int32)
        from ..train.train_step import make_serve_step
        self._step_fn = jax.jit(make_serve_step(model))
        self._chunk = min(PREFILL_CHUNK, max_len)
        self._prefill_fn = prefill_program(model)
        self._prefillq: deque = deque()     # slots awaiting prefill, FIFO
        self.steps = 0
        self.completed: List[Request] = []
        self.stats = {"admitted": 0, "drained_msgs": 0, "callback_passes": 0,
                      "prefill_chunks": 0, "prefill_tokens": 0,
                      "teacher_forced_tokens": 0}
        # per-client admitted->finished latency in engine steps (the
        # serving-layer unit: one step = one batched decode); recorded
        # only on the engine-step thread, so plain histograms suffice
        self._client_latency = [LogHistogram(1.0)
                                for _ in range(num_clients)]

    # ------------------------------------------------------- client API
    def submit(self, req: Request, client_id: int = 0) -> Request:
        """Lock-free from the caller's perspective: single-producer push
        into the client's own queue (the Submit Task Message analogue)."""
        if req.req_id is None:
            req.req_id = next(self._req_ids)
        req.client_id = client_id
        self.client_queues[client_id].submit.push(req)
        return req

    # ---------------------------------------------------- manager logic
    def _free_slots(self) -> int:
        return sum(1 for s in self.slots if s.free)

    def _pump_to_scopes(self) -> None:
        """Runtime-backed request layer: move drained client-queue
        entries onto the REAL runtime as per-client scope tasks. The
        per-client ``("reqchain",) INOUT`` chain (scope-qualified by the
        keying shim, so clients never alias) keeps each client FIFO;
        WHICH client's chain advances first is the scope layer's
        weighted-fair admission, replacing the engine's private
        round-robin. Task bodies append to the GIL-atomic admission
        buffer the engine step admits from.

        The pumping thread first claims its own runtime submit slot:
        scope submissions ride per-thread SPSC queues, so a serving
        thread that differs from the engine's constructing thread must
        not share the main slot with a concurrently-submitting main
        thread (size ``num_clients`` one larger when stepping from a
        dedicated thread)."""
        self.runtime._ensure_client_slot()
        for cid, q in enumerate(self.client_queues):
            if not q.acquire_submit():
                continue
            try:
                while True:
                    req = q.submit.pop()
                    if req is None:
                        break
                    self._scopes[cid].task(
                        self._admitq.append, req,
                        deps=[(("reqchain",), "inout")],
                        label=f"req{req.req_id}")
                    self.stats["drained_msgs"] += 1
            finally:
                q.release_submit()

    def scope_admission(self) -> Dict[str, dict]:
        """Per-client fairness counters from the runtime's admission
        layer (runtime-backed engines only)."""
        return {sc.name:
                self.runtime.placement.scope_admission(sc.scope_id)
                for sc in self._scopes}

    def _admit_requests(self) -> None:
        """DDAST callback port: round-robin client queues, up to
        MAX_OPS_THREAD per queue, early-exit once MIN_READY slots filled
        (ready tasks == occupied slots waiting to run). Each drain pass
        admits its batch longest-remaining-chain first (the scheduling
        subsystem's bottom levels over the request DAG) so a long
        request starts decoding before short ones fill the slots.

        Runtime-backed engines skip the private drain discipline: the
        scope layer already ordered requests into the admission buffer;
        this just fills free slots from it."""
        if self.runtime is not None:
            self._pump_to_scopes()
            batch: List[Request] = []
            while self._free_slots() - len(batch) > 0:
                try:
                    batch.append(self._admitq.popleft())
                except IndexError:
                    break
            for req in self._admission_order(batch):
                self._admit(req)
            return
        p = self.ddast
        self.stats["callback_passes"] += 1
        spins = max(p.max_spins, 1)
        while self._free_slots() > 0 and spins > 0:
            total = 0
            batch: List[Request] = []
            for q in self.client_queues:
                if self._free_slots() - len(batch) == 0:
                    break
                cnt = 0
                if q.acquire_submit():
                    try:
                        while cnt < p.max_ops_thread and \
                                self._free_slots() - len(batch) > 0:
                            req = q.submit.pop()
                            if req is None:
                                break
                            batch.append(req)
                            cnt += 1
                    finally:
                        q.release_submit()
                total += cnt
            for req in self._admission_order(batch):
                self._admit(req)
            self.stats["drained_msgs"] += total
            spins = spins - 1 if total == 0 else spins
            if total == 0:
                break

    @staticmethod
    def _admission_order(batch: List[Request]) -> List[Request]:
        """Order one drain pass's admissions by descending bottom level
        of each request's prefill->decode chain (shared DAG core,
        core/sched — the serving analogue of the runtime's critical-path
        placement). Stable: equal chains keep their FIFO order."""
        if len(batch) < 2:
            return batch
        nodes = []
        for req in batch:
            nodes.append(DagNode(("prefill", req.req_id),
                                 cost=max(len(req.prompt), 1)))
            nodes.append(DagNode(("decode", req.req_id),
                                 cost=max(req.max_new_tokens, 1),
                                 deps=[("prefill", req.req_id)]))
        idx, succs, _ = build_arrays(nodes)
        levels = bottom_levels(succs, [n.cost for n in nodes])
        return sorted(batch, reverse=True,
                      key=lambda r: levels[idx[("prefill", r.req_id)]])

    def _admit(self, req: Request) -> None:
        for i, slot in enumerate(self.slots):
            if slot.free:
                slot.req = req
                slot.pos = 0
                slot.prompt_left = len(req.prompt)
                req.admitted_step = self.steps
                self._pos[i] = 0
                if self._prefill_fn is not None:
                    # no reset: rows past the prompt are written by the
                    # decode step before kv_len lets it read them
                    self._prefillq.append(i)
                else:
                    self._tokens[i] = req.prompt[0]
                    self._reset_slot_cache(i)
                self.stats["admitted"] += 1
                return
        raise RuntimeError("no free slot")

    def _reset_slot_cache(self, i: int) -> None:
        """Zero slot i's cache lanes (batch index i across the pytree)."""
        def zero(c):
            if c.ndim >= 2 and c.shape[1] == self.B:
                return c.at[:, i].set(0)
            return c
        self.cache = jax.tree.map(zero, self.cache)

    # ----------------------------------------------------------- stepping
    def step(self) -> int:
        """One engine iteration: drain client queues (manager), run the
        prefill FIFO head's next chunk, then one batched decode step.
        Returns number of occupied slots advanced."""
        tr = self.tracer
        on = tr.enabled
        if on:
            t = tr.clock()
            admitted = self.stats["admitted"]
        self._admit_requests()
        if on:
            t = tr.span(SPAN_ADMIT, 0, t, self.stats["admitted"] - admitted)
        active = [i for i, s in enumerate(self.slots) if not s.free]
        if not active:
            return 0
        chunked = self._prefill_fn is not None
        lanes = [i for i in active
                 if not (chunked and self.slots[i].prompt_left)]
        prefilled, first_tok = -1, None
        if self._prefillq:
            n, prefilled, first_tok = self._prefill_next()
            if on:
                t = tr.span(SPAN_PREFILL, 0, t, n)
        next_tok = None
        if lanes:
            next_tok, _, self.cache = self._step_fn(
                self.params, self.cache, jnp.asarray(self._tokens),
                jnp.asarray(self._pos))
        if on:
            t = tr.span(SPAN_DISPATCH, 0, t)
        next_tok, first_tok = jax.device_get((next_tok, first_tok))
        if on:
            t = tr.span(SPAN_READBACK, 0, t)
        self.steps += 1
        for i in lanes:
            slot = self.slots[i]
            slot.pos += 1
            if slot.prompt_left:        # teacher-forced through decode
                slot.prompt_left -= 1
                self.stats["teacher_forced_tokens"] += 1
                if slot.prompt_left:
                    self._tokens[i] = slot.req.prompt[slot.pos]
                    self._pos[i] = slot.pos
                    continue
            self._emit(i, int(next_tok[i]))
        if first_tok is not None:
            self._emit(prefilled, int(first_tok))
        if on:
            tr.span(SPAN_TRACK, 0, t)
        return len(active)

    def _prefill_next(self):
        """Launch the FIFO head's next prompt chunk into its cache rows.
        Returns (the chunk's prompt tokens, slot, the slot's first token
        on the device) after its last chunk, else (tokens, -1, None).
        Until then the slot's decode position is where its next chunk
        starts, so the decode step's write there is overwritten before
        anything reads it."""
        i = self._prefillq[0]
        slot = self.slots[i]
        n = min(self._chunk, slot.prompt_left)
        tokens = np.zeros((self._chunk,), np.int32)
        tokens[:n] = slot.req.prompt[slot.pos:slot.pos + n]
        tok, self.cache = self._prefill_fn(
            self.params, self.cache, tokens, np.int32(i),
            np.int32(slot.pos), np.int32(n))
        slot.pos += n
        slot.prompt_left -= n
        self._pos[i] = slot.pos
        self.stats["prefill_chunks"] += 1
        self.stats["prefill_tokens"] += n
        if slot.prompt_left:
            return n, -1, None
        self._prefillq.popleft()
        return n, i, tok

    def _emit(self, i: int, tok: int) -> None:
        """Append slot i's next output token; free the slot if done."""
        slot = self.slots[i]
        req = slot.req
        req.output.append(tok)
        self._tokens[i] = tok
        if len(req.output) >= req.max_new_tokens or \
                tok == self.eos_id or slot.pos + 1 >= self.max_len:
            req.finished_step = self.steps
            if 0 <= req.client_id < len(self._client_latency):
                self._client_latency[req.client_id].record(
                    req.finished_step - req.admitted_step)
            req.done_event.set()
            self.completed.append(req)
            slot.req = None
            return
        self._pos[i] = slot.pos

    def _backlog(self) -> int:
        """Requests not yet in a batch slot: client queues, plus (when
        runtime-backed) in-flight scope tasks and the admission buffer."""
        n = sum(len(q.submit) for q in self.client_queues)
        n += len(self._admitq)
        for sc in self._scopes:
            n += sc.root.num_children_alive
        return n

    # ----------------------------------------------------- observability
    def metrics_snapshot(self) -> Dict[str, Any]:
        """JSON-friendly serving metrics: engine gauges plus one entry
        per client — request-latency histogram (in engine steps) and,
        for runtime-backed engines, the scope layer's admission
        counters and SLO attainment (``client_deadlines=``)."""
        clients: Dict[str, Any] = {}
        for cid in range(len(self.client_queues)):
            entry: Dict[str, Any] = {}
            hist = self._client_latency[cid]
            if hist.count:
                entry["latency_steps"] = hist.snapshot()
            if self._scopes:
                sc = self._scopes[cid]
                entry["admission"] = \
                    self.runtime.placement.scope_admission(sc.scope_id)
                slo = sc.slo_snapshot()
                if slo is not None:
                    entry["slo"] = slo
            clients[f"client{cid}"] = entry
        return {
            "time_unit": "s",
            "gauges": {"steps": self.steps,
                       "admitted": self.stats["admitted"],
                       "backlog": self._backlog(),
                       "free_slots": self._free_slots()},
            "clients": clients,
        }

    def metrics_text(self) -> str:
        return prometheus_text(self.metrics_snapshot())

    def serve_metrics(self, port: int = 0):
        """Start a Prometheus scrape endpoint (text format 0.0.4) on
        localhost in a daemon thread; ``port=0`` picks a free port.
        Returns ``(server, port)`` — call ``server.shutdown()`` when
        done. Every GET /metrics renders a fresh snapshot, so scrapes
        observe the run in flight."""
        import http.server
        engine = self

        class _Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):          # noqa: N802 (stdlib API name)
                if self.path.split("?")[0] not in ("/metrics", "/"):
                    self.send_error(404)
                    return
                body = engine.metrics_text().encode("utf-8")
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):
                pass                   # scrapes must not spam stderr

        srv = http.server.ThreadingHTTPServer(("127.0.0.1", port),
                                              _Handler)
        threading.Thread(target=srv.serve_forever,
                         name="metrics-scrape", daemon=True).start()
        return srv, srv.server_address[1]

    def run_until_drained(self, max_steps: int = 10_000) -> None:
        idle = 0
        for _ in range(max_steps):
            n = self.step()
            if n == 0:
                if self._backlog() == 0:
                    idle += 1
                    if idle > 2:
                        return
            else:
                idle = 0
