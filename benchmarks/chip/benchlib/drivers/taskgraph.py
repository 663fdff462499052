"""Blocked matrix products on the task runtime, graph after graph.

Each graph is the one `core/taskgraph_apps.run_matmul` submits: for every
output block C[i, j] a chain of nb tasks C[i, j] += A[i, k] @ B[k, j]
(dependences A IN, B IN, C INOUT), whose body is the program's jitted
`_gemm_block`, on float32 blocks made on the device from the seed. A
graph ends with `taskwait` and `block_until_ready` on every C block; the
next is submitted after it (closed loop). `graph_ms` is the window over
the graphs it completed.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List, Tuple

from .. import traffic as gen
from ..common import Outcome, Run, Window, span
from ..device import memory_peak_bytes

IN, INOUT = "in", "inout"


def submit_graph(rt, body, ab, bb, nb: int, bs: int, dtype) -> Dict:
    """One graph of run_matmul's shape; returns the C blocks once done."""
    import jax
    import jax.numpy as jnp
    with span("alloc"):
        cb: Dict[Tuple[int, int], object] = {
            (i, j): jnp.zeros((bs, bs), dtype) for i in range(nb)
            for j in range(nb)}

    def gemm(i: int, j: int, k: int) -> None:
        cb[(i, j)] = body(ab[i * nb + k], bb[k * nb + j], cb[(i, j)])

    with span("submit"):
        for i in range(nb):
            for j in range(nb):
                for k in range(nb):
                    rt.task(gemm, i, j, k,
                            deps=[(("A", i, k), IN), (("B", k, j), IN),
                                  (("C", i, j), INOUT)],
                            label=f"gemm{i}.{j}.{k}")
    with span("taskwait"):
        rt.taskwait()
    with span("block_until_ready"):
        jax.block_until_ready(list(cb.values()))
    return cb


def run(r: Run, body=None) -> Outcome:
    import jax
    import jax.numpy as jnp
    from repro.core import TaskRuntime
    from repro.core.ddast import DDASTParams
    from repro.core.taskgraph_apps import _gemm_block

    from ..weights import matrix_blocks
    body = body or _gemm_block
    c, tr = r.config, r.traffic
    n, bs = int(tr["n"]), int(tr["block"])
    nb = n // bs
    dtype = jnp.dtype(c["dtype"])
    rt = TaskRuntime(num_workers=int(c["num_workers"]), mode=c["mode"],
                     params=DDASTParams(**c["ddast_params"]))
    rt.start()
    try:
        ab = matrix_blocks(n, bs, r.seed, 1)
        bb = matrix_blocks(n, bs, r.seed, 2)
        jax.block_until_ready((ab, bb))
        r.mark("matrices")
        submit_graph(rt, body, ab, bb, nb, bs, dtype)      # warm-up graph
        r.mark("warm")
        keep_at = int(gen.rng_for(r.seed, 6).integers(1, 4))
        kept: List[Tuple[int, Dict]] = []
        graphs = 0
        st0 = dict(rt.policy.stats())
        tasks0 = rt.stats.tasks_executed
        compiles0 = r.compiles.count
        t0 = time.perf_counter()
        setup_s = t0 - r.t_start
        window = Window(r) if r.trace else None
        traced_graphs = 0
        end = t0 + r.seconds
        while True:
            if window is not None and graphs == 1 and window.t0 is None:
                window.start()
            cb = submit_graph(rt, body, ab, bb, nb, bs, dtype)
            graphs += 1
            if window is not None and window.t0 is not None \
                    and window.t1 is None:
                traced_graphs += 1
                if time.perf_counter() - window.t0 >= min(
                        2.0, r.seconds / 4) or time.perf_counter() >= end:
                    window.stop()
            if graphs == keep_at:
                kept.append((graphs, cb))
            if time.perf_counter() >= end:
                break
        t1 = time.perf_counter()
        reduced = window.reduce() if window is not None else None
        if graphs != keep_at:
            kept.append((graphs, cb))
        compiles_window = r.compiles.count - compiles0
        st1 = dict(rt.policy.stats())
        tasks = rt.stats.tasks_executed - tasks0
    finally:
        rt.shutdown()
    peak = memory_peak_bytes(r.devices)
    e2e = {"setup_s": setup_s, "graph_ms": 1e3 * (t1 - t0) / graphs}
    layer = {"tasks": tasks,
             "lock_wait_s": st1["lock_wait_s"] - st0["lock_wait_s"],
             "messages": st1["messages_processed"]
             - st0["messages_processed"],
             "gemm_block": (bs, dtype.itemsize),
             "traced_graphs": traced_graphs, "tasks_per_graph": nb ** 3}
    notes = {"window": f"{graphs} graphs of {nb ** 3} tasks in "
                       f"{t1 - t0:.3f} s; {tasks} tasks executed",
             "compiles_in_window": compiles_window}

    # ---- correctness: the kept graphs' C blocks against the reference
    answers = [(g, [cb[(i, j)] for i in range(nb) for j in range(nb)])
               for g, cb in kept]
    del ab, bb, cb, kept, rt
    gc.collect()
    t_ref = time.perf_counter()
    res = r.reference.check_blocks(c, r.seed, n, bs,
                                   [blocks for _, blocks in answers],
                                   control=r.control)
    checks = {"block_err_max": (res["block_err_max"],
                                float(r.limits["block_err_max"]))}
    control = {}
    if r.control:
        control["block_err_max"] = res["control_block_err_max"]
    notes["reference"] = (f"graphs {[g for g, _ in answers]} of {graphs}, "
                          f"{time.perf_counter() - t_ref:.1f} s")
    return Outcome(e2e=e2e, attempted=graphs, failed=0, checks=checks,
                   memory_peak_bytes=peak, layer=layer, reduced=reduced,
                   control=control, notes=notes)
