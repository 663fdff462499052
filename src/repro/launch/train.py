"""End-to-end trainer. The host side runs the paper's runtime: a
TaskRuntime in ddast mode whose idle workers execute the registered
callbacks — DDAST message handling, data prefetch and async checkpoint
flushing — so the main thread only dispatches device steps.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --tiny \
      --steps 50 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config, tiny_config
from repro.core import TaskRuntime
from repro.launch.compile_cache import use_compile_cache
from repro.models.registry import get_model
from repro.train.checkpoint import CheckpointManager
from repro.train.data import DataConfig, Prefetcher, SyntheticLM
from repro.train.fault import HeartbeatMonitor
from repro.train.optimizer import OptConfig, init_opt_state
from repro.train.train_step import TrainConfig, make_train_step


def train(arch: str, tiny: bool, steps: int, batch: int, seq: int,
          ckpt_dir: str, microbatches: int = 1, resume: bool = True,
          log_every: int = 10, schedule_steps: int = 0) -> dict:
    cfg = tiny_config(arch) if tiny else get_config(arch)
    model = get_model(cfg)
    tcfg = TrainConfig(opt=OptConfig(peak_lr=1e-3, warmup_steps=20,
                                     total_steps=schedule_steps or steps),
                       num_microbatches=microbatches)
    # params and optimizer state are replaced every step: donating them
    # lets the step write the new ones in place instead of keeping both
    # copies live (4.9 GB more for qwen2-0.5b at published widths)
    step_fn = jax.jit(make_train_step(model, tcfg), donate_argnums=(0, 1))

    params = model.init_params(jax.random.key(0))
    opt = init_opt_state(params)

    # host runtime: idle threads do prefetch + checkpoint I/O (DDAST org)
    rt = TaskRuntime(num_workers=2, mode="ddast")
    ds = SyntheticLM(cfg, DataConfig(batch=batch, seq_len=seq))
    prefetch = Prefetcher(ds, rt.dispatcher, depth=4)
    ckpt = CheckpointManager(ckpt_dir, rt.dispatcher)
    hb = HeartbeatMonitor(hosts=[f"host{i}" for i in range(1)])

    start_step = 0
    if resume:
        restored = ckpt.restore({"params": params, "opt": opt})
        if restored is not None:
            start_step, tree = restored
            params, opt = tree["params"], tree["opt"]
            print(f"[train] resumed from step {start_step}")

    losses = []
    rt.start()
    try:
        t0 = time.time()
        for step in range(start_step, steps):
            batch_np = prefetch.get(step)
            batch_dev = {k: jnp.asarray(v) for k, v in batch_np.items()}
            if cfg.is_encoder_decoder:
                batch_dev["frames"] = jnp.zeros(
                    (batch, cfg.encoder_seq, cfg.d_model), cfg.jnp_dtype)
            st = time.time()
            params, opt, metrics = step_fn(params, opt, batch_dev)
            loss = float(metrics["loss"])
            losses.append(loss)
            hb.beat("host0", step, time.time() - st)
            if step % log_every == 0 or step == steps - 1:
                print(f"[train] step {step} loss {loss:.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"gnorm {float(metrics['grad_norm']):.2f}")
            if step and step % 20 == 0:
                ckpt.save(step, {"params": params, "opt": opt})
        ckpt.save(steps, {"params": params, "opt": opt}, blocking=True)
        wall = time.time() - t0
    finally:
        ckpt.flush()
        rt.shutdown()
    return {"losses": losses, "wall_s": wall,
            "prefetch_async": prefetch.fills_async,
            "ckpt_writes": ckpt.async_writes,
            "final_loss": losses[-1] if losses else None,
            "step_fn": step_fn, "params": params, "opt": opt}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="qwen2-0.5b")
    ap.add_argument("--tiny", action="store_true", default=True)
    ap.add_argument("--full", dest="tiny", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None,
                    help="resume from and save to this directory "
                         "(default: a fresh temporary one)")
    args = ap.parse_args()
    use_compile_cache()
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="repro_ckpt_")
    out = train(args.arch, args.tiny, args.steps, args.batch, args.seq,
                ckpt_dir, args.microbatches)
    print(f"[train] done: final loss {out['final_loss']:.4f} "
          f"({out['wall_s']:.1f}s, {out['prefetch_async']} async prefetches, "
          f"{out['ckpt_writes']} ckpt writes to {ckpt_dir})")


if __name__ == "__main__":
    main()
