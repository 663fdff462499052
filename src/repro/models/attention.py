"""Grouped-query attention with the features the assigned pool needs:
GQA (any nq/nkv ratio), optional QKV bias (Qwen2), sliding-window local
attention + attn-logit softcapping (Gemma-2), cross-attention (Whisper),
RoPE or NoPE. Train path, single-token decode path with KV cache, and a
chunk-prefill path that writes a chunk of one slot's prompt into it.

The inner attention math routes through `repro.kernels.ops.attention`,
which dispatches to the Pallas flash kernel on TPU and to the pure-jnp
reference elsewhere — the kernel and this module share one contract
(structured causal/window/kv_len arguments, never materialized masks, so
the flash kernel can exploit them for block skipping).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels import ops as kops
from ..parallel.collectives import constrain
from .config import ModelConfig
from .layers import apply_rope, rope_cos_sin

Params = Dict[str, Any]


def init_attention(rng: jax.Array, cfg: ModelConfig,
                   cross: bool = False) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    dt = cfg.jnp_dtype
    k = jax.random.split(rng, 4)
    s = (1.0 / d) ** 0.5
    p = {"wq": jax.random.normal(k[0], (d, nq * hd), dt) * s,
         "wk": jax.random.normal(k[1], (d, nkv * hd), dt) * s,
         "wv": jax.random.normal(k[2], (d, nkv * hd), dt) * s,
         "wo": jax.random.normal(k[3], (nq * hd, d), dt) * s}
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq * hd,), dt)
        p["bk"] = jnp.zeros((nkv * hd,), dt)
        p["bv"] = jnp.zeros((nkv * hd,), dt)
    _ = cross
    return p


def _project_q(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    b, s, _ = x.shape
    q = constrain(x @ p["wq"], "dp", None, "model")
    if cfg.qkv_bias:
        q = q + p["bq"]
    return q.reshape(b, s, cfg.num_heads, cfg.resolved_head_dim)


def _project_kv(cfg: ModelConfig, p: Params,
                x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    b, s, _ = x.shape
    k = constrain(x @ p["wk"], "dp", None, "model")
    v = constrain(x @ p["wv"], "dp", None, "model")
    if cfg.qkv_bias:
        k = k + p["bk"]
        v = v + p["bv"]
    hd = cfg.resolved_head_dim
    return (k.reshape(b, s, cfg.num_kv_heads, hd),
            v.reshape(b, s, cfg.num_kv_heads, hd))


def attention_train(cfg: ModelConfig, p: Params, x: jax.Array,
                    local: bool = False, use_rope: bool = True,
                    memory: Optional[jax.Array] = None,
                    causal: bool = True) -> jax.Array:
    """Full-sequence attention. `memory` given -> cross-attention (no
    causal mask, no rope). `causal=False` + no memory -> bidirectional
    self-attention (whisper encoder)."""
    b, s, _ = x.shape
    q = _project_q(cfg, p, x)
    kv_src = memory if memory is not None else x
    k, v = _project_kv(cfg, p, kv_src)
    if memory is None and use_rope:
        pos = jnp.arange(s)
        cos, sin = rope_cos_sin(pos, cfg.resolved_head_dim, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    is_causal = causal and memory is None
    o = kops.attention(q, k, v, causal=is_causal,
                       window=cfg.sliding_window if (local and is_causal) else None,
                       softcap=cfg.attn_softcap)
    return constrain(o.reshape(b, s, -1) @ p["wo"], "dp", None, None)


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    hd = cfg.resolved_head_dim
    shape = (batch, max_len, cfg.num_kv_heads, hd)
    return {"k": jnp.zeros(shape, cfg.jnp_dtype),
            "v": jnp.zeros(shape, cfg.jnp_dtype)}


def attention_decode(cfg: ModelConfig, p: Params, x: jax.Array,
                     cache: Params, pos: jax.Array, local: bool = False,
                     use_rope: bool = True,
                     memory_kv: Optional[Params] = None
                     ) -> Tuple[jax.Array, Params]:
    """One-token decode. x [B,1,d]; cache k/v [B,L,nkv,hd]; pos scalar.
    `memory_kv` given -> cross-attention against precomputed encoder KV
    (cache passes through unchanged)."""
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    q = _project_q(cfg, p, x)                        # [B,1,nq,hd]
    # decode activations are replicated on the model axis: the cache is
    # context-parallel (length on "model"), so attention reduces over the
    # sharded length with per-step psums — head-sharded activations would
    # misalign with GQA head counts and gather the cache instead
    q = constrain(q, "dp", None, None, None)
    if memory_kv is not None:
        o = kops.attention(q, memory_kv["k"], memory_kv["v"],
                           softcap=cfg.attn_softcap)
        return o.reshape(b, 1, -1) @ p["wo"], cache
    kn, vn = _project_kv(cfg, p, x)                  # [B,1,nkv,hd]
    pos_b = jnp.broadcast_to(pos, (b,))              # scalar or per-slot [B]
    if use_rope:
        cos, sin = rope_cos_sin(pos_b[:, None], hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)                  # cos/sin [B,1,hd/2]
        kn = apply_rope(kn, cos, sin)

    if jnp.ndim(pos) == 0:
        # uniform position (the large-scale serving path): a single
        # dynamic_update_slice keeps the batch-sharded cache update local.
        # The vmap'd per-slot variant lowers to a scatter that SPMD can
        # only realize by replicating the cache (dry-run measured ~cache-
        # sized all-gathers per step).
        k = jax.lax.dynamic_update_slice(
            cache["k"], kn.astype(cache["k"].dtype), (0, pos, 0, 0))
        v = jax.lax.dynamic_update_slice(
            cache["v"], vn.astype(cache["v"].dtype), (0, pos, 0, 0))
    else:
        def _ins(c, upd, p_):
            return jax.lax.dynamic_update_slice(c, upd.astype(c.dtype),
                                                (p_, 0, 0))

        k = jax.vmap(_ins)(cache["k"], kn, pos_b)
        v = jax.vmap(_ins)(cache["v"], vn, pos_b)
    o = kops.attention(q, k, v, kv_len=pos_b + 1,
                       window=cfg.sliding_window if local else None,
                       softcap=cfg.attn_softcap)
    return o.reshape(b, 1, -1) @ p["wo"], {"k": k, "v": v}


def attention_prefill(cfg: ModelConfig, p: Params, x: jax.Array,
                      row: Params, start: jax.Array, local: bool = False
                      ) -> Tuple[jax.Array, Params]:
    """One prefill chunk of one slot. x [1,C,d] holds positions
    start..start+C-1; row k/v [L,nkv,hd] is the slot's cache row, filled
    below `start`. Returns (out [1,C,d], {"k","v"} [C,nkv,hd]): the row's
    C positions from min(start, L-C) with the chunk written in, so a
    caller storing them there never has them shifted by a clamped
    update."""
    c = x.shape[1]
    hd = cfg.resolved_head_dim
    q = _project_q(cfg, p, x)                        # [1,C,nq,hd]
    kn, vn = _project_kv(cfg, p, x)                  # [1,C,nkv,hd]
    cos, sin = rope_cos_sin(start + jnp.arange(c), hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    kn = apply_rope(kn, cos, sin)
    max_len = row["k"].shape[0]

    def insert(r, new):
        # padded by C so that the chunk lands at `start` unclamped; the
        # padding is visible only to queries past the prompt
        r = jnp.concatenate([r, jnp.zeros((c,) + r.shape[1:], r.dtype)])
        return jax.lax.dynamic_update_slice_in_dim(r, new[0].astype(r.dtype),
                                                   start, axis=0)

    k, v = insert(row["k"], kn), insert(row["v"], vn)
    o = kops.attention(q, k[None], v[None], causal=True, q_offset=start,
                       window=cfg.sliding_window if local else None,
                       softcap=cfg.attn_softcap)
    at = jnp.minimum(start, max_len - c)
    return o.reshape(1, c, -1) @ p["wo"], {
        "k": jax.lax.dynamic_slice_in_dim(k, at, c, axis=0),
        "v": jax.lax.dynamic_slice_in_dim(v, at, c, axis=0)}


def precompute_cross_kv(cfg: ModelConfig, p: Params,
                        memory: jax.Array) -> Params:
    k, v = _project_kv(cfg, p, memory)
    return {"k": k, "v": v}
