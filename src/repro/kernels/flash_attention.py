"""Flash attention (online softmax) as a Pallas TPU kernel.

TPU adaptation (not a CUDA port): the grid's minor-most dimension iterates
sequentially on a core, so the running max/denominator/accumulator live in
VMEM scratch that persists across KV blocks — no atomics, no shared-memory
banking games. Tiles are MXU-aligned (q/kv blocks x head_dim lanes).

Supports: GQA (q heads grouped onto kv heads), causal masking,
sliding-window locality (Gemma-2), attn-logit softcapping. Causal/window
block skipping is done with `pl.when` on block indices, so fully-masked
KV blocks cost nothing on TPU.

The backward pass has no kernel of its own: the VJP recomputes the
reference attention and differentiates that, so a train step keeps the
kernel in its forward pass (Pallas calls have no autodiff rule).

Oracle: kernels/ref.py::attention_ref (tests sweep shapes/dtypes in
interpret mode).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import attention_ref

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, causal: bool, window: Optional[int],
                 softcap: Optional[float], blk_q: int, blk_k: int,
                 seq_k: int):
    kb = pl.program_id(3)
    qb = pl.program_id(2)
    nkb = pl.num_programs(3)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qb * blk_q
    k_start = kb * blk_k

    # block-level skip: causal => kv block strictly after q block is dead;
    # window => kv block entirely before the window is dead
    live = True
    if causal:
        live = k_start <= q_start + blk_q - 1
    if window is not None:
        live = jnp.logical_and(live,
                               k_start + blk_k - 1 > q_start - window)

    @pl.when(live if not isinstance(live, bool) else True)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)       # [blk_q, hd]
        k = k_ref[0, 0].astype(jnp.float32)       # [blk_k, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (blk_q, blk_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (blk_q, blk_k), 1)
        mask = kpos < seq_k
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                        # [blk_q, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_scr[...] = m_new

    @pl.when(kb == nkb - 1)
    def _done():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = False, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    blk_q: int = 128, blk_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q [B,S,nq,hd]; k/v [B,T,nkv,hd] -> [B,S,nq,hd]."""
    return _flash(q, k, v, causal, window, softcap, blk_q, blk_k, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, window, softcap, blk_q, blk_k, interpret):
    return _flash_forward(q, k, v, causal, window, softcap, blk_q, blk_k,
                          interpret)


def _flash_fwd(q, k, v, causal, window, softcap, blk_q, blk_k, interpret):
    out = _flash_forward(q, k, v, causal, window, softcap, blk_q, blk_k,
                         interpret)
    return out, (q, k, v)


def _flash_bwd(causal, window, softcap, blk_q, blk_k, interpret, res, g):
    del blk_q, blk_k, interpret
    _, vjp = jax.vjp(functools.partial(attention_ref, causal=causal,
                                       window=window, softcap=softcap), *res)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _flash_forward(q, k, v, causal, window, softcap, blk_q, blk_k,
                   interpret):
    b, s, nq, hd = q.shape
    t, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    scale = hd ** -0.5
    blk_q = min(blk_q, s)
    blk_k = min(blk_k, t)
    s_pad = math.ceil(s / blk_q) * blk_q
    t_pad = math.ceil(t / blk_k) * blk_k
    qt = jnp.moveaxis(q, 2, 1)                    # [B,nq,S,hd]
    kt = jnp.moveaxis(k, 2, 1)                    # [B,nkv,T,hd]
    vt = jnp.moveaxis(v, 2, 1)
    if s_pad != s:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
    if t_pad != t:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, t_pad - t), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, t_pad - t), (0, 0)))

    grid = (b, nq, s_pad // blk_q, t_pad // blk_k)
    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, blk_q=blk_q, blk_k=blk_k, seq_k=t)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, hd),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, blk_k, hd),
                         lambda bi, hi, qi, ki, g_=g: (bi, hi // g_, ki, 0)),
            pl.BlockSpec((1, 1, blk_k, hd),
                         lambda bi, hi, qi, ki, g_=g: (bi, hi // g_, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, hd),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nq, s_pad, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    out = out[:, :, :s]
    return jnp.moveaxis(out, 1, 2)
