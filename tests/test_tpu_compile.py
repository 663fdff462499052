"""The main path's Pallas kernels, compiled for one TPU v5e chip at real
widths with no chip attached: the TPU compiler is installed and compiles
for a described topology. Each case asserts the kernel survived as a
``tpu_custom_call`` in the compiled program. Interpret mode (the oracle
tests in test_kernels.py) cannot catch what only this compiler refuses:
block shapes off the tiling, primitives Mosaic does not lower, too much
VMEM. One more case compiles the whole qwen2-0.5b train step at
published widths and checks that it fits one chip.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gemm import moe_gemm_pallas
from repro.kernels.ssm_scan import selective_scan_pallas, ssm_scan_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 (any failure means: cannot describe)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile_text(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


BF16, F32 = jnp.bfloat16, jnp.float32

CASES = {
    # qwen2-0.5b: 14 query / 2 kv heads of 64, causal over 2048 tokens
    "flash_qwen2_0_5b": (
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        [((1, 2048, 14, 64), BF16), ((1, 2048, 2, 64), BF16),
         ((1, 2048, 2, 64), BF16)]),
    # 32 query / 8 kv heads of 128 under a 4096-token sliding window
    "flash_window_4096": (
        lambda q, k, v: flash_attention(q, k, v, causal=True, window=4096),
        [((1, 8192, 32, 128), BF16), ((1, 8192, 8, 128), BF16),
         ((1, 8192, 8, 128), BF16)]),
    # qwen2-moe-a2.7b: 60 experts padded to 64, d_model 2048 -> 1408
    "moe_gemm_qwen2_moe": (
        moe_gemm_pallas,
        [((64, 256, 2048), BF16), ((64, 2048, 1408), BF16)]),
    # jamba-v0.1: Mamba inner width 8192 (2 x 4096), state 16
    "selective_scan_jamba": (
        selective_scan_pallas,
        [((1, 1024, 8192), BF16), ((1, 1024, 8192), BF16),
         ((8192, 16), F32), ((1, 1024, 16), BF16), ((1, 1024, 16), BF16),
         ((8192,), F32)]),
    # batch 2: the state's block must tile over the batch
    "ssm_scan_batch2": (
        ssm_scan_pallas,
        [((2, 1024, 2048), BF16), ((2, 1024, 2048), BF16)]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_persistent_cache):
    fn, shapes = CASES[case]
    assert "tpu_custom_call" in _compile_text(fn, shapes, one_chip)


def test_qwen2_0_5b_train_step_fits_one_chip(one_chip, no_persistent_cache,
                                             monkeypatch):
    """The full-width step chip_smoke.py trains (4 x 1024 tokens, params
    and optimizer state donated) fits one v5e's 16 GB with the flash
    kernel in it."""
    from repro.configs import get_config
    from repro.models.registry import get_model
    from repro.train.optimizer import init_opt_state
    from repro.train.train_step import TrainConfig, make_train_step

    # kernels.ops asks the default backend (here the CPU) whether to take
    # the kernels' TPU branch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = get_model(get_config("qwen2-0.5b"))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(model.init_params, jax.random.key(0)))
    opt = on_chip(jax.eval_shape(init_opt_state, params))
    batch = on_chip({k: jax.ShapeDtypeStruct((4, 1024), jnp.int32)
                     for k in ("tokens", "labels")})
    compiled = jax.jit(make_train_step(model, TrainConfig()),
                       donate_argnums=(0, 1)).lower(params, opt,
                                                    batch).compile()
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert m.alias_size_in_bytes > 0.9 * m.argument_size_in_bytes
    assert live < 16e9
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen2_0_5b_prefill_chunk_donates_cache(one_chip, no_persistent_cache):
    """The serving engine's chunk prefill at the chat cell's shapes (128
    slots x 1024): the cache is updated in place, and the program's name
    stays apart from the decode step's."""
    from repro.configs import get_config
    from repro.models.registry import get_model
    from repro.serve.engine import PREFILL_CHUNK, prefill_program

    model = get_model(get_config("qwen2-0.5b"))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(model.init_params, jax.random.key(0)))
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(128, 1024)))
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    tokens = jax.ShapeDtypeStruct((PREFILL_CHUNK,), jnp.int32,
                                  sharding=one_chip)
    compiled = prefill_program(model).lower(params, cache, tokens, i32, i32,
                                            i32).compile()
    m = compiled.memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
    assert m.alias_size_in_bytes == cache_bytes
    assert m.temp_size_in_bytes < 0.05 * cache_bytes
    text = compiled.as_text()
    assert text.startswith("HloModule jit_prefill_chunk")
    assert "jit_serve_step" not in text
