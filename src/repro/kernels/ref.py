"""Pure-jnp oracles for every Pallas kernel. These define the numerical
contract: kernels must match these within tolerance across the shape/dtype
sweeps in tests/test_kernels.py."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = False, window: Optional[int] = None,
                  kv_len: Optional[jax.Array] = None,
                  softcap: Optional[float] = None,
                  scale: Optional[float] = None,
                  q_offset: Optional[jax.Array] = None) -> jax.Array:
    """GQA attention oracle.

    q [B,S,nq,hd]; k/v [B,T,nkv,hd] with nq % nkv == 0.
    causal     — standard causal mask (queries at positions T-S..T-1)
    window     — additionally restrict to a trailing sliding window
    kv_len     — scalar or [B]: only keys < kv_len are valid (decode)
    softcap    — tanh softcapping of attention logits (Gemma-2)
    q_offset   — scalar: queries sit at positions q_offset..q_offset+S-1
                 instead of T-S..T-1 (a prefill chunk against a cache row)
    """
    b, s, nq, hd = q.shape
    t, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    scale = hd ** -0.5 if scale is None else scale
    qg = q.reshape(b, s, nkv, g, hd)
    # operands stay bf16 (collectives move the narrow copy); the MXU-style
    # f32 accumulation comes from preferred_element_type
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        scores = jnp.tanh(scores / softcap) * softcap
    kpos = jnp.arange(t)
    if kv_len is not None:
        # decode: query position is kv_len-1 (cache padded to t)
        kv = jnp.asarray(kv_len)
        if kv.ndim == 0:
            kv = kv[None]
        valid = kpos[None, :] < kv[:, None]          # [B,T]
        if window is not None:
            valid &= kpos[None, :] > (kv[:, None] - 1) - window
        m5 = valid[:, None, None, None, :]           # [B,1,1,1,T]
    else:
        # align the query block to the sequence end, or to q_offset
        qpos = jnp.arange(s) + (t - s if q_offset is None else q_offset)
        mask = jnp.ones((s, t), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        m5 = mask[None, None, None]
    scores = jnp.where(m5, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bkgst,btkh->bskgh", w.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, s, nq, hd).astype(q.dtype)


def ssm_scan_ref(a: jax.Array, bx: jax.Array,
                 h0: Optional[jax.Array] = None) -> jax.Array:
    """Linear recurrence oracle: h_t = a_t * h_{t-1} + bx_t, returns all
    h_t. a/bx [B, S, ...] (elementwise)."""
    if h0 is None:
        h0 = jnp.zeros_like(bx[:, 0])

    def step(h, ab):
        at, bt = ab
        h = at * h + bt
        return h, h

    _, hs = jax.lax.scan(step, h0.astype(jnp.float32),
                         (jnp.moveaxis(a, 1, 0).astype(jnp.float32),
                          jnp.moveaxis(bx, 1, 0).astype(jnp.float32)))
    return jnp.moveaxis(hs, 0, 1).astype(bx.dtype)


def selective_scan_ref(x: jax.Array, dt: jax.Array, a_log: jax.Array,
                       b: jax.Array, c: jax.Array, d: jax.Array,
                       h0: Optional[jax.Array] = None
                       ) -> tuple[jax.Array, jax.Array]:
    """Fused Mamba selective scan oracle (never materializes [B,S,D,N]).

    x/dt [B,S,D]; a_log [D,N] (A = -exp(a_log)); b/c [B,S,N]; d [D].
    h_t = exp(dt_t A) h_{t-1} + dt_t b_t x_t ;  y_t = h_t c_t + d x_t.
    Returns (y [B,S,D], h_last [B,D,N]).
    """
    bsz = x.shape[0]
    n = a_log.shape[1]
    dd = x.shape[2]
    if h0 is None:
        h0 = jnp.zeros((bsz, dd, n), jnp.float32)
    a = -jnp.exp(a_log.astype(jnp.float32))

    def step(h, inp):
        xt, dtt, bt, ct = inp                       # [B,D],[B,D],[B,N],[B,N]
        da = jnp.exp(dtt[..., None] * a[None])      # [B,D,N]
        h = da * h + (dtt * xt)[..., None] * bt[:, None, :]
        y = jnp.einsum("bdn,bn->bd", h, ct)
        return h, y

    xs = (jnp.moveaxis(x, 1, 0).astype(jnp.float32),
          jnp.moveaxis(dt, 1, 0).astype(jnp.float32),
          jnp.moveaxis(b, 1, 0).astype(jnp.float32),
          jnp.moveaxis(c, 1, 0).astype(jnp.float32))
    s = x.shape[1]
    chunk = 128
    if s % chunk == 0 and s > chunk:
        # chunked remat: backward stores only chunk-boundary carries,
        # never the [B,D,N] state trail for every step
        nc = s // chunk
        xs = jax.tree.map(
            lambda t: t.reshape((nc, chunk) + t.shape[1:]), xs)

        @jax.checkpoint
        def chunk_body(h, xc):
            return jax.lax.scan(step, h, xc)

        h_last, ys = jax.lax.scan(chunk_body, h0, xs)
        ys = ys.reshape((s,) + ys.shape[2:])
    else:
        h_last, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1) + x.astype(jnp.float32) * d[None, None]
    return y.astype(x.dtype), h_last


def moe_gemm_ref(x: jax.Array, w: jax.Array) -> jax.Array:
    """Grouped (per-expert) matmul oracle: x [E,C,d] @ w [E,d,f] -> [E,C,f],
    accumulating in f32. Inputs stay in their dtype (bf16 on the wire) —
    casting BEFORE the einsum would make SPMD collectives move f32 copies
    (dry-run measured 2x MoE exchange bytes)."""
    return jnp.einsum("ecd,edf->ecf", x, w,
                      preferred_element_type=jnp.float32).astype(x.dtype)
