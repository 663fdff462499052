"""Serving launcher: spins up the continuous-batching engine (a tiny
config by default, the published one with --full) and runs a synthetic
request workload from several client threads.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
      --requests 16 --clients 4
  PYTHONPATH=src python -m repro.launch.serve --full --max-len 512 \
      --max-prompt 64
"""
from __future__ import annotations

import argparse
import threading
import time

import jax
import numpy as np

from repro.configs import ARCHS, get_config, tiny_config
from repro.launch.compile_cache import use_compile_cache
from repro.models.registry import get_model
from repro.serve.engine import Request, ServeEngine


def serve(arch: str, num_requests: int, clients: int, slots: int = 4,
          max_new: int = 8, tiny: bool = True, max_len: int = 64,
          max_prompt: int = 9) -> dict:
    """Prompts are 2..max_prompt random token ids. Returns the run's
    counts and times, and the engine itself (``"engine"``) with its
    completed requests, model and parameters."""
    cfg = tiny_config(arch) if tiny else get_config(arch)
    if cfg.is_encoder_decoder:
        raise SystemExit("serve launcher targets decoder-only archs")
    model = get_model(cfg)
    params = model.init_params(jax.random.key(0))
    eng = ServeEngine(model, params, batch_slots=slots, max_len=max_len,
                      num_clients=clients)
    rng = np.random.RandomState(0)
    reqs = [Request(prompt=rng.randint(1, cfg.vocab_size,
                                       rng.randint(2, max_prompt + 1)
                                       ).tolist(),
                    max_new_tokens=max_new) for _ in range(num_requests)]

    def client(cid: int) -> None:
        for i, r in enumerate(reqs):
            if i % clients == cid:
                eng.submit(r, client_id=cid)
                time.sleep(0.001)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(clients)]
    t0 = time.time()
    for t in threads:
        t.start()
    # engine thread = the DDAST manager draining client queues
    while len(eng.completed) < num_requests:
        eng.step()
        if time.time() - t0 > 600:
            raise RuntimeError("serve timeout")
    for t in threads:
        t.join()
    wall = time.time() - t0
    toks = sum(len(r.output) for r in eng.completed)
    return {"wall_s": wall, "requests": len(eng.completed),
            "tokens": toks, "engine_steps": eng.steps,
            "tok_per_s": toks / wall, "stats": eng.stats, "engine": eng}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="qwen2-0.5b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--full", dest="tiny", action="store_false",
                    help="the published config instead of the tiny one")
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--max-prompt", type=int, default=9)
    args = ap.parse_args()
    use_compile_cache()
    out = serve(args.arch, args.requests, args.clients, args.slots,
                tiny=args.tiny, max_len=args.max_len,
                max_prompt=args.max_prompt)
    print(f"[serve] {out['requests']} requests, {out['tokens']} tokens in "
          f"{out['wall_s']:.1f}s ({out['tok_per_s']:.1f} tok/s, "
          f"{out['engine_steps']} engine steps)")
    print(f"[serve] scheduler stats: {out['stats']}")


if __name__ == "__main__":
    main()
