"""Training through the program's jitted, donated train step.

The step is `train/train_step.make_train_step` jitted with
`donate_argnums=(0, 1)`, as `launch/train.py` builds it. Set-up makes the
parameters on the device from the seed and drives that one compiled step
through its first `checked_steps` steps, recording what correctness
compares: each step's loss, the per-leaf norms of the first gradient as
the optimizer got it (its first moment after one step over 1 - b1), and
the per-leaf norms of the parameters' change over those steps. The same
step, parameters and optimizer state then run the window. Rows are packed
documents from the seed, every step's different; the host makes the next
batch while the device runs the current step, with two steps in flight.

`train_tokens_per_s` is all tokens of the steps completed in the window
over the window, the last step ended by `block_until_ready`.
"""
from __future__ import annotations

import gc
import time
from collections import deque

from .. import traffic as gen
from ..common import Outcome, Run, Window, span
from ..device import memory_peak_bytes
from ..weights import generate, leaf_norms


def run(r: Run, wrap_step=None) -> Outcome:
    import jax
    import jax.numpy as jnp
    from repro.models.registry import get_model
    from repro.train.optimizer import OptConfig, init_opt_state
    from repro.train.train_step import TrainConfig, make_train_step

    c, tr, arch = r.config, r.traffic, r.arch
    dec = arch.counts(c)
    model = get_model(arch.model_config(c))
    ocfg = OptConfig(**tr["opt"])
    tcfg = TrainConfig(opt=ocfg, z_loss=float(tr["z_loss"]))
    step_fn = jax.jit(make_train_step(model, tcfg),
                      donate_argnums=(0, 1))
    if wrap_step is not None:
        step_fn = wrap_step(step_fn)
    b, s = int(tr["batch"]), int(tr["seq_len"])
    vocab = c["vocab_size"]

    def feed(i):
        return {k: jnp.asarray(v)
                for k, v in gen.packed_rows(tr, r.seed, i, vocab).items()}

    norms = jax.jit(lambda t: leaf_norms(arch.from_program(t),
                                         arch.UNSTACKED))
    change = jax.jit(lambda a, z: leaf_norms(arch.from_program(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, z)),
        arch.UNSTACKED))

    params = generate(arch.weight_shapes(c), r.seed, c["torch_dtype"],
                      convert=lambda w: arch.to_program(model, w))
    opt = init_opt_state(params)
    p0 = jax.tree.map(jnp.copy, params)
    jax.block_until_ready((params, opt, p0))
    r.mark("weights")
    checked = int(tr["checked_steps"])
    losses, g1 = [], None
    for i in range(checked):
        params, opt, m = step_fn(params, opt, feed(i))
        losses.append(m["loss"])
        if i == 0:
            g1 = norms(jax.tree.map(lambda x: x / (1.0 - ocfg.b1), opt["m"]))
    moved = change(params, p0)
    del p0
    losses = [float(x) for x in losses]
    g1 = {k: float(v) for k, v in g1.items()}
    moved = {k: float(v) for k, v in moved.items()}
    jax.block_until_ready((params, opt))
    r.mark("checked_steps")

    # ---- the window: the same step and state, two steps in flight
    window = Window(r) if r.trace else None
    traced_steps = 0
    compiles0 = r.compiles.count
    step = checked
    done = 0
    inflight: deque = deque()
    t0 = time.perf_counter()
    setup_s = t0 - r.t_start
    end = t0 + r.seconds
    while True:
        if window is not None and done == 1 and window.t0 is None:
            window.start()
        with span("feed"):
            batch = feed(step)
        with span("step"):
            params, opt, m = step_fn(params, opt, batch)
        inflight.append(m["loss"])
        step += 1
        done += 1
        if len(inflight) > 2:
            with span("wait"):
                inflight.popleft().block_until_ready()
        if window is not None and window.t0 is not None and window.t1 is None:
            traced_steps += 1
            if traced_steps >= 4 or time.perf_counter() >= end:
                jax.block_until_ready(m["loss"])
                window.stop()
        if time.perf_counter() >= end:
            break
    jax.block_until_ready((params, opt))
    t1 = time.perf_counter()
    reduced = window.reduce() if window is not None else None
    compiles_window = r.compiles.count - compiles0
    peak = memory_peak_bytes(r.devices)

    e2e = {"setup_s": setup_s,
           "train_tokens_per_s": done * b * s / (t1 - t0)}
    layer = {"train_step_flops": dec.train_step_flops(b, s),
             "flash_fwd_call": (b, s, dec.heads, dec.kv_heads, dec.head_dim)}
    notes = {"window": f"{done} steps of {b}x{s} tokens in "
                       f"{t1 - t0:.3f} s after {checked} checked steps",
             "compiles_in_window": compiles_window,
             "losses": losses}

    del params, opt, m, inflight, batch
    gc.collect()
    t_ref = time.perf_counter()
    batches = [gen.packed_rows(tr, r.seed, i, vocab) for i in range(checked)]
    res = r.reference.train_gaps(c, r.seed, batches, tr["opt"],
                                 float(tr["z_loss"]), losses, g1, moved,
                                 control=r.control)
    lim = r.limits
    checks = {k: (res[k], float(lim[k]))
              for k in ("loss_gap", "grad_gap", "change_gap")}
    control = res.get("control", {})
    notes["reference"] = (f"{checked} steps, "
                          f"{time.perf_counter() - t_ref:.1f} s; "
                          f"excluded leaves {res['excluded']}; worst "
                          f"{res['worst']}")
    return Outcome(e2e=e2e, attempted=done, failed=0, checks=checks,
                   memory_peak_bytes=peak, layer=layer, reduced=reduced,
                   control=control, notes=notes)
