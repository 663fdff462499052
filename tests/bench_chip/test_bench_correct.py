"""`correct` on the CPU at tiny sizes: the harness's look for a chip is
skipped and the rest of a run is driven, with the timed path sound,
broken underneath, or replaced by the control.

Each fault a cell can have must make `correct` false: a step that
returns its state unchanged, half of the batch left out, a token or an
answer altered where it is produced (one chip: no exchange between chips
to leave out). The control (the reference one precision step down), put
in the program's place and judged by the harness's own comparison, must
come out not correct, and read well above the program. The limits are
the cells' own, set on the chip at the cells' sizes.
"""
import importlib

import jax
import jax.numpy as jnp
import pytest

from benchpath import BENCH  # noqa: F401
import run as bench
from benchlib.common import judge

SEED = 2 ** 31 + 99
# wide enough that the sound program reads within the cells' limits and
# the controls beyond them, as at the cells' sizes on the chip: training
# at a quarter of the widths, serving at the cell's widths (the largest
# logit gap grows with them), both with two layers
TINY_MODEL = dict(hidden_size=256, intermediate_size=512,
                  num_attention_heads=4, num_key_value_heads=2,
                  num_hidden_layers=2, vocab_size=4096)
SERVE_MODEL = dict(TINY_MODEL, hidden_size=896, intermediate_size=4864,
                   num_attention_heads=14)


def tiny_run(cell, control=False, root=bench.ROOT, bench_dir=bench.BENCH):
    """The driver of the cell, and its Run cut to the tiny sizes above;
    `root` and `bench_dir` name another checkout's benchmark."""
    args = bench.parse(["--workload", cell, "--seed", str(SEED),
                        "--seconds", "1", "--trace", "0",
                        "--control", str(int(control))])
    man, c, r = bench.prepare(args, root, bench_dir, devices=jax.devices())
    kind = r.traffic["kind"]
    if kind == "serve":
        r.config.update(SERVE_MODEL)
        r.seconds = 6.0                   # six requests due at one a second
        r.traffic.update(slots=4, max_len=64, rate_per_s=1, warmup_s=0.3,
                         drain_limit_s=30,
                         prompt={"median": 10, "sigma": 0.9, "min": 2,
                                 "max": 32},
                         output={"median": 16, "sigma": 0.7, "min": 2,
                                 "max": 32})
    elif kind == "taskgraph":
        r.traffic.update(n=128, block=32)
    else:
        r.config.update(TINY_MODEL)
        r.traffic.update(batch=2, seq_len=32, eos_id=1,
                         doc={"median": 8, "sigma": 1.0, "min": 2, "max": 40})
    from benchlib.common import CompileCounter
    r.compiles = CompileCounter()
    return importlib.import_module(f"benchlib.drivers.{kind}"), r


# ---------------------------------------------------------------- serving
def test_serve_sound_and_control_separates():
    driver, r = tiny_run("qwen2-0.5b.chat", control=True)
    out = driver.run(r)
    assert out.correct, out.checks
    assert out.failed == 0 and out.attempted > 0
    assert out.control["gap_max"] > 3 * out.checks["gap_max"][0]
    assert not judge(out.controls()["control"])


def test_serve_altered_token_is_caught(monkeypatch):
    from repro.train import train_step

    real = train_step.make_serve_step

    def altered(model):
        step = real(model)

        def serve_step(params, cache, tokens, pos):
            tok, logits, cache = step(params, cache, tokens, pos)
            return (tok + 1) % model.cfg.vocab_size, logits, cache
        return serve_step
    monkeypatch.setattr(train_step, "make_serve_step", altered)
    driver, r = tiny_run("qwen2-0.5b.chat")
    out = driver.run(r)
    assert not out.correct, out.checks


# ------------------------------------------------------------- task graph
@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered"])
def test_taskgraph_fault_is_caught(fault):
    from repro.core.taskgraph_apps import _gemm_block
    body = {"state_unchanged": lambda a, b, c: c,
            "answer_altered": lambda a, b, c: _gemm_block(a, b, c) * 1.01,
            }[fault]
    driver, r = tiny_run("taskrt-ddast.gemm16k-b1024")
    assert not driver.run(r, body=body).correct


def test_taskgraph_control_fails_its_limit():
    driver, r = tiny_run("taskrt-ddast.gemm16k-b1024", control=True)
    out = driver.run(r)
    assert out.control["block_err_max"] > r.limits["block_err_max"]
    assert not judge(out.controls()["control"])


# --------------------------------------------------------------- training
def test_train_control_separates():
    # the control reads the first steps' losses well above the program,
    # and, in the program's place, is judged not correct; so is the half
    # batch planted in the reference
    driver, r = tiny_run("qwen2-0.5b.train", control=True)
    out = driver.run(r)
    assert out.correct, out.checks
    assert out.control["loss_gap"] > 3 * out.checks["loss_gap"][0]
    judged = {name: judge(ch) for name, ch in out.controls().items()}
    assert judged == {"control": False, "half_batch": False}, \
        out.controls()


def _unchanged(step_fn):
    def step(params, opt, batch):
        copy = jax.tree.map(jnp.copy, (params, opt))
        _, _, metrics = step_fn(*copy, batch)
        return params, opt, metrics
    return step


def _half_batch(step_fn):
    def step(params, opt, batch):
        half = batch["tokens"].shape[0] // 2
        return step_fn(params, opt, {k: v[:half] for k, v in batch.items()})
    return step


@pytest.mark.parametrize("fault", [_unchanged, _half_batch])
def test_train_fault_is_caught(fault):
    driver, r = tiny_run("qwen2-0.5b.train")
    out = driver.run(r, wrap_step=fault)
    assert not out.correct, out.checks
