#!/usr/bin/env python3
"""Smoke run of the main path on one TPU chip, through the package's own
entry points, at published widths:

    python3 chip_smoke.py

Phases, in order; each prints its own lines, and any failure exits
non-zero:

  device   JAX's first device must be a TPU. There is no CPU fallback, and
           REPRO_FORCE_REF / REPRO_FORCE_PALLAS (which would take the
           kernels off the chip) are refused.
  runtime  The paper's task runtime driving the chip: blocked matmul
           (4x4 blocks of 512) on TaskRuntime in ddast and in sharded mode,
           each block body a jitted device op. Checked against a float64
           product, and bitwise across the two modes.
  serve    qwen2-0.5b through repro.launch.serve.serve: 8 requests over 4
           slots, max_len 512, prompts of up to 64 tokens. Every request
           completes, every id is below vocab_size, and one request's
           decode-step logits agree with model.forward's.
  train    qwen2-0.5b through repro.launch.train.train: 4 steps of 4 x 1024
           tokens from a fresh checkpoint directory. Losses are finite and
           the compiled step holds the flash kernel (tpu_custom_call).

Weights are random, from a fixed seed. Everything runs in this one
process, which holds the chip; it starts no child process. The last line
of standard output is one JSON object naming the device. The wall times
printed are smoke timings, not benchmark numbers.
"""
from __future__ import annotations

import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "qwen2-0.5b"

# runtime phase: C = A @ B, 2048 x 2048 in 4 x 4 blocks of 512
MM_N, MM_BLOCK = 2048, 512
# The TPU's default precision for a float32 matmul rounds both operands to
# bfloat16 (8 significant bits, unit roundoff 2**-9) and accumulates in
# float32. With unit-normal inputs an entry of C sums K=2048 products, so
# its rounding error is a random walk of about 2**-9 * sqrt(2K / 3) and
# its magnitude about sqrt(K). The bound on max|C - C64| / sqrt(K) is
# 2e-2, five times 2**-8 and about twice the largest such error expected
# over 4M entries.
MM_TOL = 2e-2

# serve phase
SERVE_REQUESTS, SERVE_SLOTS, SERVE_CLIENTS = 8, 4, 4
SERVE_MAX_LEN, SERVE_MAX_PROMPT, SERVE_MAX_NEW = 512, 64, 16
# Decode (one token at a time against the KV cache, reference attention)
# and forward (the whole sequence, flash kernel) run in bfloat16 through
# 24 layers along different operation orders. Each bfloat16 rounding is
# worth up to 2**-9 of the value, so their logits agree to a few percent
# of the logits' range, not to float32 precision: the bound is 5% of
# max|forward logits|.
LOGIT_RTOL = 5e-2

# train phase
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 4, 4, 1024


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    print(f"[smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def phase_device():
    for var in ("REPRO_FORCE_REF", "REPRO_FORCE_PALLAS"):
        if os.environ.get(var):
            fail(f"{var} is set; it would keep the kernels off the chip")
    import jax
    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        fail(f"no TPU found: JAX's device is {dev.platform!r}; this smoke "
             "run does not fall back to it")
    return dev, len(devices)


def phase_runtime() -> None:
    import numpy as np
    from repro.core import TaskRuntime
    from repro.core.taskgraph_apps import run_matmul

    rng = np.random.default_rng(0)
    a = rng.standard_normal((MM_N, MM_N), dtype=np.float32)
    b = rng.standard_normal((MM_N, MM_N), dtype=np.float32)
    want = a.astype(np.float64) @ b.astype(np.float64)
    nb = MM_N // MM_BLOCK
    results = {}
    for mode in ("ddast", "sharded"):
        rt = TaskRuntime(num_workers=4, mode=mode)
        rt.start()
        t0 = time.perf_counter()
        try:
            c = run_matmul(rt, a, b, MM_BLOCK)
        finally:
            rt.shutdown()
        wall = time.perf_counter() - t0
        err = float(np.abs(c - want).max()) / math.sqrt(MM_N)
        log(f"runtime {mode}: {nb}x{nb} blocks of {MM_BLOCK}, "
            f"{rt.stats.tasks_executed} tasks, max|C-C64|/sqrt(K)={err!r} "
            f"(bound {MM_TOL}); smoke timing {wall:.3f}s")
        check(rt.stats.tasks_executed == nb ** 3,
              f"{mode}: {rt.stats.tasks_executed} tasks ran, "
              f"expected {nb ** 3}")
        check(err <= MM_TOL, f"{mode}: matmul error {err} over {MM_TOL}")
        results[mode] = c
    check(np.array_equal(results["ddast"], results["sharded"]),
          "ddast and sharded results differ")
    log("runtime: ddast and sharded results are bitwise identical")


def phase_serve() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.serve import serve
    from repro.train.train_step import make_serve_step

    t0 = time.perf_counter()
    out = serve(ARCH, SERVE_REQUESTS, SERVE_CLIENTS, SERVE_SLOTS,
                max_new=SERVE_MAX_NEW, tiny=False, max_len=SERVE_MAX_LEN,
                max_prompt=SERVE_MAX_PROMPT)
    wall = time.perf_counter() - t0
    eng = out["engine"]
    cfg = eng.model.cfg
    log(f"serve: {cfg.name} at published widths "
        f"(d_model={cfg.d_model}, layers={cfg.repeats}, "
        f"vocab_size={cfg.vocab_size}), {out['requests']} requests, "
        f"{out['tokens']} tokens, {out['engine_steps']} engine steps; "
        f"smoke timing {wall:.3f}s including compilation")
    reqs = eng.completed
    check(len(reqs) == SERVE_REQUESTS,
          f"{len(reqs)} of {SERVE_REQUESTS} requests completed")
    for r in reqs:
        check(r.done_event.is_set() and len(r.output) == SERVE_MAX_NEW,
              f"request {r.req_id}: {len(r.output)} of {SERVE_MAX_NEW} "
              "tokens")
        bad = [t for t in r.output if not 0 <= t < cfg.vocab_size]
        check(not bad, f"request {r.req_id}: ids {bad} outside the "
                       f"vocabulary of {cfg.vocab_size}")
    log(f"serve: all {len(reqs)} requests completed; every id is below "
        f"vocab_size {cfg.vocab_size}")

    # one request, teacher-forced through the serve step one position at
    # a time, against the forward pass over the same tokens
    req = max(reqs, key=lambda r: len(r.prompt))
    toks = req.prompt + req.output[:-1]
    step = jax.jit(make_serve_step(eng.model))
    cache = eng.model.init_cache(1, eng.max_len)
    rows = []
    for pos, tok in enumerate(toks):
        _, logits, cache = step(eng.params, cache,
                                jnp.asarray([tok], jnp.int32),
                                jnp.asarray([pos], jnp.int32))
        rows.append(logits[0])
    dec = np.asarray(jnp.stack(rows), np.float32)
    fwd, _ = jax.jit(eng.model.forward)(
        eng.params, {"tokens": jnp.asarray([toks], jnp.int32)})
    fwd = np.asarray(fwd[0, :, :cfg.vocab_size], np.float32)
    check(np.isfinite(dec).all() and np.isfinite(fwd).all(),
          "non-finite logits")
    diff = float(np.abs(dec - fwd).max())
    scale = float(np.abs(fwd).max())
    log(f"serve: request {req.req_id}, {len(toks)} positions: "
        f"max|decode - forward| = {diff!r}, max|forward| = {scale!r}, "
        f"ratio {diff / scale!r} (bound {LOGIT_RTOL})")
    check(diff <= LOGIT_RTOL * scale,
          f"decode logits differ from forward logits by {diff}")


def phase_train() -> None:
    import jax.numpy as jnp
    from repro.launch.train import train

    ckpt_dir = tempfile.mkdtemp(prefix="repro_smoke_ckpt_")
    t0 = time.perf_counter()
    try:
        out = train(ARCH, tiny=False, steps=TRAIN_STEPS, batch=TRAIN_BATCH,
                    seq=TRAIN_SEQ, ckpt_dir=ckpt_dir, resume=False,
                    log_every=1)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    wall = time.perf_counter() - t0
    losses = out["losses"]
    log(f"train: {ARCH} at published widths, {len(losses)} steps of "
        f"{TRAIN_BATCH}x{TRAIN_SEQ} tokens, losses {losses}; smoke timing "
        f"{wall:.3f}s including compilation and the final checkpoint")
    check(len(losses) == TRAIN_STEPS, f"{len(losses)} steps ran")
    check(all(math.isfinite(x) for x in losses), "non-finite loss")
    batch = {k: jnp.zeros((TRAIN_BATCH, TRAIN_SEQ), jnp.int32)
             for k in ("tokens", "labels")}
    text = out["step_fn"].lower(out["params"], out["opt"],
                                batch).compile().as_text()
    kernels = text.count("tpu_custom_call")
    log(f"train: compiled step holds {kernels} tpu_custom_call sites")
    check(kernels > 0, "the compiled train step holds no Pallas kernel")


def main() -> None:
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"the repro package is not beside this script ({ROOT}/src)")
    sys.path.insert(0, str(ROOT / "src"))
    dev, count = phase_device()
    from repro.launch.compile_cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")
    for name, phase in (("runtime", phase_runtime), ("serve", phase_serve),
                        ("train", phase_train)):
        t0 = time.perf_counter()
        phase()
        gc.collect()
        log(f"{name}: passed (smoke timing {time.perf_counter() - t0:.3f}s)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}),
        flush=True)


if __name__ == "__main__":
    main()
