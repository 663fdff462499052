"""Open-loop serving through `ServeEngine.submit` / `ServeEngine.step`.

A generator thread pushes seeded requests into the engine's client
queues at their due times (the traffic file's rate, Poisson arrivals)
from the moment set-up ends; the main thread steps the engine, as a
serving process would. Arrivals run through a warm-up, so the window sees
steady occupancy, and go on until every request due in the window has
finished or the drain limit has passed.

Latency is taken on the host clock: time to first token from a request's
due time to the end of the step that produced its first token; the gaps
between output tokens from the ends of the steps that produced them. In
a traced run the clock stops while the profiler is stopped (which takes
seconds): arrivals wait and no request is charged that time.
"""
from __future__ import annotations

import gc
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import List, Optional

from .. import traffic as gen
from ..common import Outcome, Run, Window, percentile, span
from ..device import memory_peak_bytes
from ..weights import generate


@dataclass
class Tracked:
    due: float                    # Clock time it was due
    req: object                   # repro.serve.engine.Request
    sent: float = 0.0
    admitted: float = -1.0        # end of the step that admitted it
    tokens: List[float] = field(default_factory=list)   # token times
    token_steps: List[int] = field(default_factory=list)  # and engine steps
    done: bool = False


class Clock:
    """`perf_counter` less the seconds spent paused. The loop pauses it
    while the profiler stops; the generator submits nothing meanwhile."""

    def __init__(self) -> None:
        self.cond = threading.Condition()
        self.paused_s = 0.0
        self.paused_at: Optional[float] = None
        self.stopped = False

    def now(self) -> float:
        return time.perf_counter() - self.paused_s

    @contextmanager
    def paused(self):
        with self.cond:
            self.paused_at = time.perf_counter()
        try:
            yield
        finally:
            with self.cond:
                self.paused_s += time.perf_counter() - self.paused_at
                self.paused_at = None
                self.cond.notify_all()

    def stop(self) -> None:
        with self.cond:
            self.stopped = True
            self.cond.notify_all()

    def wait_until(self, t: float) -> bool:
        """Waits until the clock reads t and is not paused (True), or is
        stopped (False). Call with `cond` held."""
        while not self.stopped:
            if self.paused_at is not None:
                self.cond.wait()
                continue
            left = t - self.now()
            if left <= 0:
                return True
            self.cond.wait(left)
        return False


class Generator(threading.Thread):
    """Pushes each request into its client queue when it is due."""

    def __init__(self, engine, arrivals, seed: int, vocab: int,
                 t0: float, clock: Clock):
        super().__init__(name="bench-generator", daemon=True)
        self.engine, self.arrivals = engine, arrivals
        self.seed, self.vocab, self.t0, self.clock = seed, vocab, t0, clock
        self.sent: List[Tracked] = []     # appended here, read by the loop

    def run(self) -> None:
        from repro.serve.engine import Request
        clock = self.clock
        for i, a in enumerate(self.arrivals):
            req = Request(prompt=gen.prompt_tokens(self.seed, i, a.prompt_len,
                                                   self.vocab),
                          max_new_tokens=a.output_len)
            tr = Tracked(self.t0 + a.due_s, req)
            with clock.cond:
                if not clock.wait_until(tr.due):
                    return
                self.engine.submit(req, client_id=a.client)
                tr.sent = clock.now()
            self.sent.append(tr)


def run(r: Run) -> Outcome:
    import jax
    from repro.models.registry import get_model
    from repro.serve.engine import Request, ServeEngine

    c, tr, arch = r.config, r.traffic, r.arch
    dec = arch.counts(c)
    model = get_model(arch.model_config(c))
    params = generate(arch.weight_shapes(c), r.seed, c["torch_dtype"],
                      convert=lambda w: arch.to_program(model, w))
    jax.block_until_ready(params)
    r.mark("weights")
    engine = ServeEngine(model, params, batch_slots=tr["slots"],
                         max_len=tr["max_len"], num_clients=tr["clients"])
    jax.block_until_ready(engine.cache)
    r.mark("engine")
    # warm every program the window drives: the decode step, the prompt
    # chunk (or, for a model without one, the per-admission cache reset),
    # the token upload
    warm = engine.submit(Request(prompt=[1, 2], max_new_tokens=2))
    while not warm.done_event.is_set():
        engine.step()
    jax.block_until_ready(engine.cache)
    warm_steps = engine.steps
    # prompts go through the chunk program, else are teacher-forced
    # through the decode step a token a step
    chunked = engine.stats["prefill_chunks"] > 0
    r.mark("warm")

    warmup_s, drain_s = float(tr["warmup_s"]), float(tr["drain_limit_s"])
    arrivals = gen.open_loop(tr, r.seed, (warmup_s, r.seconds, drain_s))

    clock = Clock()
    t0 = clock.now()
    setup_s = t0 - r.t_start
    ws, we = t0 + warmup_s, t0 + warmup_s + r.seconds
    g = Generator(engine, arrivals, r.seed, c["vocab_size"], t0, clock)
    n_due_window = sum(1 for a in arrivals
                       if warmup_s <= a.due_s < warmup_s + r.seconds)
    compiles0 = r.compiles.count
    win_steps = [None, None]
    win_stats = [None, None]          # the engine's counters at both ends
    trace_at = ws + min(5.0, r.seconds / 4)
    trace_s = min(3.0, r.seconds / 4)
    window = Window(r) if r.trace else None
    traced_steps = [None, None]
    stop_s = 0.0                      # the clock paused for the profiler

    seen = 0
    longest = (0.0, 0.0)              # longest loop pass, and when it ended
    pending: List[Tracked] = []       # submitted, not yet admitted
    active: List[Tracked] = []        # admitted, not finished
    window_reqs: List[Tracked] = []
    g.start()
    try:
        while True:
            now = clock.now()
            if win_steps[0] is None and now >= ws:
                win_steps[0] = engine.steps
                win_stats[0] = dict(engine.stats)
            if now >= we:
                if win_steps[1] is None:
                    win_steps[1] = engine.steps
                    win_stats[1] = dict(engine.stats)
                    compiles_window = r.compiles.count - compiles0
                w_sent = len(window_reqs)
                if (w_sent == n_due_window
                        and all(t.done for t in window_reqs)):
                    break
                if now >= we + drain_s:
                    break
            if window is not None:
                if traced_steps[0] is None and now >= trace_at:
                    window.start()
                    traced_steps[0] = engine.steps
                elif traced_steps[1] is None and traced_steps[0] is not None \
                        and now >= trace_at + trace_s:
                    traced_steps[1] = engine.steps
                    t_stop = time.perf_counter()
                    with clock.paused():
                        window.stop()
                    stop_s = time.perf_counter() - t_stop
            with span("step"):
                n = engine.step()
            t = clock.now()
            with span("track"):
                while seen < len(g.sent):
                    x = g.sent[seen]
                    seen += 1
                    pending.append(x)
                    if ws <= x.due < we:
                        window_reqs.append(x)
                still = []
                for x in pending:
                    if x.req.admitted_step >= 0:
                        x.admitted = t
                        active.append(x)
                    else:
                        still.append(x)
                pending = still
                keep = []
                for x in active:
                    k = len(x.req.output)
                    while len(x.tokens) < k:
                        x.tokens.append(t)
                        x.token_steps.append(engine.steps)
                    if x.req.finished_step >= 0:
                        x.done = True
                    else:
                        keep.append(x)
                active = keep
            if n == 0:
                time.sleep(0.0005)
            if t - now > longest[0]:
                longest = (t - now, t - t0)
    finally:
        clock.stop()
        g.join(timeout=10.0)
    t_end = clock.now()
    if window is not None and traced_steps[1] is None \
            and traced_steps[0] is not None:
        traced_steps[1] = engine.steps
        window.stop()
    reduced = window.reduce() if window is not None else None
    if win_steps[1] is None:
        win_steps[1] = engine.steps
        win_stats[1] = dict(engine.stats)
        compiles_window = r.compiles.count - compiles0
    lateness = [x.sent - x.due for x in g.sent]
    peak = memory_peak_bytes(r.devices)

    # ---- end-to-end metrics over the window's requests
    missing = [x for x in window_reqs if not x.done]
    ttft = [(x.tokens[0] if x.tokens else t_end) - x.due
            for x in window_reqs]
    itl = [b - a for x in window_reqs for a, b in zip(x.tokens, x.tokens[1:])]
    e2e = {"setup_s": setup_s,
           "ttft_p95_ms": 1e3 * percentile(ttft, 95) if ttft else float("nan"),
           "itl_p95_ms": 1e3 * percentile(itl, 95) if itl else float("nan")}

    # ---- per-layer inputs: the work of the decode steps traced
    s0, s1 = win_steps
    layer = {"prefill_chunked": chunked,
             "admit_wait_s": [x.admitted - x.due for x in window_reqs
                              if x.admitted >= 0],
             "serve_steps_traced": None, "serve_flops_traced": None}
    if traced_steps[0] is not None:
        k0, k1 = traced_steps
        layer["serve_steps_traced"] = k1 - k0
        layer["serve_flops_traced"] = dec.decode_step_flops(
            decode_contexts(g.sent, chunked, k0, k1))
    w0, w1 = win_stats
    prompt_path = {k: w1[k] - w0[k] for k in (
        "prefill_chunks", "prefill_tokens", "teacher_forced_tokens")}

    notes = {
        "window": f"{len(window_reqs)} requests due ({n_due_window} "
                  f"scheduled), {len(missing)} unfinished; engine steps "
                  f"{s1 - s0} in the window, {engine.steps} in all "
                  f"({warm_steps} warm-up)",
        "compiles_in_window": compiles_window,
        "prompt_path_in_window": prompt_path,
        "profiler_stop_s": f"{stop_s:.3f} (the clock paused)",
        "longest_loop_pass_ms": f"{1e3 * longest[0]:.1f}, ending "
                                f"{longest[1]:.1f} s after set-up",
        "generator_lateness_ms": (
            f"p50 {1e3 * percentile(lateness, 50):.3f} "
            f"p99 {1e3 * percentile(lateness, 99):.3f} "
            f"max {1e3 * max(lateness):.3f} over {len(lateness)} requests"
            if lateness else "no requests"),
    }

    # ---- correctness: free the program's state, then the reference
    finished = [x for x in window_reqs if x.done]
    sample = pick_sample(finished, int(r.limits["sample_requests"]), r.seed)
    requests = [(list(x.req.prompt), list(x.req.output)) for x in sample]
    bad_ids = sum(1 for _, out in requests for t in out
                  if not 0 <= t < c["vocab_size"])
    del engine, params, g, pending, active
    gc.collect()
    checks = {"bad_ids": (float(bad_ids), 0.0)}
    control = {}
    if requests and not bad_ids:
        t_ref = time.perf_counter()
        res = r.reference.served_gaps(c, r.seed, requests, tr["max_len"],
                                      tr["output"]["max"], control=r.control)
        checks["gap_max"] = (float(res["gap_max"]),
                             float(r.limits["gap_max"]))
        if r.control:
            control["gap_max"] = float(res["control_gap_max"])
        notes["reference"] = (f"{len(requests)} requests, "
                              f"{sum(len(o) for _, o in requests)} served "
                              f"tokens, {time.perf_counter() - t_ref:.1f} s")
    else:
        checks["gap_max"] = (float("nan"), float(r.limits["gap_max"]))
    return Outcome(e2e=e2e, attempted=len(window_reqs), failed=len(missing),
                   checks=checks, memory_peak_bytes=peak, layer=layer,
                   reduced=reduced, control=control, notes=notes)


def decode_contexts(sent: List[Tracked], chunked: bool, k0: int,
                    k1: int) -> List[int]:
    """The context (positions attended) of each token the decode step
    ran in engine steps k0+1..k1: output token j of a P-token prompt at
    P + j. Where prompts are chunked, a request's first token came from
    its last chunk and is left out; where they are teacher-forced, prompt
    token j ran at step admitted_step + 1 + j at context j + 1 (the last
    one also made output token 0)."""
    ctx = []
    for x in sent:
        p = len(x.req.prompt)
        ctx += [p + j for j, k in enumerate(x.token_steps)
                if k0 < k <= k1 and (j or not chunked)]
        if not chunked and x.req.admitted_step >= 0:
            a = x.req.admitted_step
            ctx += [j + 1 for j in range(p - 1) if k0 < a + 1 + j <= k1]
    return ctx


def pick_sample(done: List[Tracked], n: int, seed: int) -> List[Tracked]:
    """The request with the most served tokens, and n-1 more drawn from
    the seed."""
    if not done:
        return []
    longest = max(done, key=lambda x: (len(x.req.output), len(x.req.prompt)))
    rest = [x for x in done if x is not longest]
    rng = gen.rng_for(seed, 5)
    picks = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(picks)]
