"""Per-kernel validation: Pallas (interpret mode on CPU) vs the pure-jnp
oracles in kernels/ref.py, swept over shapes and dtypes, plus hypothesis
property tests of the attention contract."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gemm import moe_gemm_pallas
from repro.kernels.ssm_scan import selective_scan_pallas, ssm_scan_pallas

KEY = jax.random.key(0)


def rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ------------------------------------------------------- flash attention
ATTN_SHAPES = [
    # (B, S, T, nq, nkv, hd)
    (1, 128, 128, 4, 4, 64),
    (2, 128, 128, 8, 2, 64),       # GQA 4:1
    (1, 256, 256, 4, 1, 128),      # MQA
    (2, 64, 64, 14, 2, 64),        # qwen2-0.5b head layout
    (1, 96, 96, 4, 4, 64),         # non-multiple of block
]


@pytest.mark.parametrize("shape", ATTN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_ref(shape, dtype, causal):
    b, s, t, nq, nkv, hd = shape
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = rand(k1, (b, s, nq, hd), dtype)
    k = rand(k2, (b, t, nkv, hd), dtype)
    v = rand(k3, (b, t, nkv, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, blk_q=64, blk_k=64,
                          interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_window_and_softcap():
    b, s, nq, nkv, hd = 1, 256, 4, 2, 64
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = rand(k1, (b, s, nq, hd))
    k = rand(k2, (b, s, nkv, hd))
    v = rand(k3, (b, s, nkv, hd))
    out = flash_attention(q, k, v, causal=True, window=64, softcap=50.0,
                          blk_q=64, blk_k=64, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True, window=64, softcap=50.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [None, 64])
def test_flash_attention_grad_matches_ref(window):
    """A train step differentiates through the kernel: its VJP must give
    the reference's gradients."""
    b, s, nq, nkv, hd = 1, 128, 4, 2, 64
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = rand(k1, (b, s, nq, hd))
    k = rand(k2, (b, s, nkv, hd))
    v = rand(k3, (b, s, nkv, hd))

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v, causal=True, window=window) ** 2)

    got = jax.grad(loss(functools.partial(
        flash_attention, blk_q=64, blk_k=64, interpret=True)),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref.attention_ref), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


@given(st.integers(1, 3), st.sampled_from([64, 128]),
       st.sampled_from([(4, 4), (4, 2), (8, 1)]),
       st.booleans())
@settings(max_examples=10, deadline=None)
def test_flash_attention_property(b, s, heads, causal):
    nq, nkv = heads
    hd = 64
    k1, k2, k3 = jax.random.split(jax.random.key(b * s + nq), 3)
    q = rand(k1, (b, s, nq, hd))
    k = rand(k2, (b, s, nkv, hd))
    v = rand(k3, (b, s, nkv, hd))
    out = flash_attention(q, k, v, causal=causal, blk_q=64, blk_k=64,
                          interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
    # attention outputs are convex combinations of V rows
    assert float(jnp.abs(out).max()) <= float(jnp.abs(v).max()) + 1e-4


# -------------------------------------------------------- selective scan
SCAN_SHAPES = [(1, 128, 64, 8), (2, 256, 128, 16), (1, 512, 256, 16)]


@pytest.mark.parametrize("shape", SCAN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_selective_scan_matches_ref(shape, dtype):
    b, s, d, n = shape
    ks = jax.random.split(KEY, 5)
    x = rand(ks[0], (b, s, d), dtype)
    dt = jax.nn.softplus(rand(ks[1], (b, s, d))).astype(dtype) * 0.1
    a_log = rand(ks[2], (d, n), jnp.float32) * 0.1
    bmat = rand(ks[3], (b, s, n), dtype, 0.5)
    cmat = rand(ks[4], (b, s, n), dtype, 0.5)
    dvec = jnp.ones((d,), jnp.float32) * 0.5
    y, h = selective_scan_pallas(x, dt, a_log, bmat, cmat, dvec,
                                 blk_t=64, blk_d=64, interpret=True)
    yr, hr = ref.selective_scan_ref(x, dt, a_log, bmat, cmat, dvec)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               rtol=1e-3, atol=1e-3)


def test_selective_scan_carries_state_across_blocks():
    """Recurrence must be continuous across time-block boundaries."""
    b, s, d, n = 1, 256, 64, 8
    ks = jax.random.split(KEY, 5)
    x = rand(ks[0], (b, s, d))
    dt = jnp.full((b, s, d), 0.05)
    a_log = jnp.zeros((d, n))
    bmat = jnp.ones((b, s, n)) * 0.3
    cmat = jnp.ones((b, s, n)) * 0.3
    dvec = jnp.zeros((d,))
    y1, _ = selective_scan_pallas(x, dt, a_log, bmat, cmat, dvec,
                                  blk_t=32, blk_d=64, interpret=True)
    y2, _ = selective_scan_pallas(x, dt, a_log, bmat, cmat, dvec,
                                  blk_t=256, blk_d=64, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", [(1, 64, 128), (2, 128, 512)])
def test_linear_scan_matches_ref(shape):
    b, s, d = shape
    k1, k2 = jax.random.split(KEY)
    a = jax.nn.sigmoid(rand(k1, (b, s, d)))
    bx = rand(k2, (b, s, d))
    got = ssm_scan_pallas(a, bx, blk_t=32, blk_d=128, interpret=True)
    want = ref.ssm_scan_ref(a, bx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------- moe gemm
MOE_SHAPES = [(4, 64, 128, 256), (8, 128, 256, 128), (3, 100, 96, 72)]


@pytest.mark.parametrize("shape", MOE_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gemm_matches_ref(shape, dtype):
    e, c, d, f = shape
    k1, k2 = jax.random.split(KEY)
    x = rand(k1, (e, c, d), dtype, 0.3)
    w = rand(k2, (e, d, f), dtype, 0.3)
    got = moe_gemm_pallas(x, w, blk_c=64, blk_d=64, blk_f=64,
                          interpret=True)
    want = ref.moe_gemm_ref(x, w)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_moe_gemm_expert_isolation():
    """Each expert's output must depend only on its own slice."""
    e, c, d, f = 4, 32, 64, 64
    k1, k2 = jax.random.split(KEY)
    x = rand(k1, (e, c, d))
    w = rand(k2, (e, d, f))
    base = moe_gemm_pallas(x, w, interpret=True)
    x2 = x.at[2].set(999.0)
    pert = moe_gemm_pallas(x2, w, interpret=True)
    np.testing.assert_array_equal(np.asarray(base[0]), np.asarray(pert[0]))
    np.testing.assert_array_equal(np.asarray(base[3]), np.asarray(pert[3]))
    assert not np.allclose(np.asarray(base[2]), np.asarray(pert[2]))
