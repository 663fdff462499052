"""Jit-ready wrappers that route each hot-spot either to its Pallas TPU
kernel or to the pure-jnp oracle. The models call ONLY these entry points,
so kernels are first-class but swappable (REPRO_FORCE_REF=1 forces the
oracle; REPRO_FORCE_PALLAS=1 forces the kernel in interpret mode for CPU
validation)."""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref as _ref


def _use_pallas() -> bool:
    if os.environ.get("REPRO_FORCE_REF"):
        return False
    if os.environ.get("REPRO_FORCE_PALLAS"):
        return True
    return jax.default_backend() == "tpu"


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = False, window: Optional[int] = None,
              kv_len: Optional[jax.Array] = None,
              softcap: Optional[float] = None,
              q_offset: Optional[jax.Array] = None) -> jax.Array:
    """GQA attention; see kernels.ref.attention_ref for the contract."""
    s = q.shape[1]
    if _use_pallas() and s > 1 and kv_len is None and q_offset is None \
            and q.shape[1] == k.shape[1]:
        from .flash_attention import flash_attention
        interpret = jax.default_backend() != "tpu"
        return flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, interpret=interpret)
    return _ref.attention_ref(q, k, v, causal=causal, window=window,
                              kv_len=kv_len, softcap=softcap,
                              q_offset=q_offset)


def ssm_scan(a: jax.Array, bx: jax.Array,
             h0: Optional[jax.Array] = None) -> jax.Array:
    """Linear recurrence h_t = a_t h_{t-1} + bx_t over axis 1."""
    if _use_pallas():
        from .ssm_scan import ssm_scan_pallas
        interpret = jax.default_backend() != "tpu"
        return ssm_scan_pallas(a, bx, h0=h0, interpret=interpret)
    return _ref.ssm_scan_ref(a, bx, h0=h0)


def selective_scan(x: jax.Array, dt: jax.Array, a_log: jax.Array,
                   b: jax.Array, c: jax.Array, d: jax.Array,
                   h0: Optional[jax.Array] = None):
    """Fused Mamba selective scan -> (y [B,S,D], h_last [B,D,N])."""
    if _use_pallas():
        from .ssm_scan import selective_scan_pallas
        interpret = jax.default_backend() != "tpu"
        return selective_scan_pallas(x, dt, a_log, b, c, d, h0=h0,
                                     interpret=interpret)
    return _ref.selective_scan_ref(x, dt, a_log, b, c, d, h0=h0)


def moe_gemm(x: jax.Array, w: jax.Array) -> jax.Array:
    """Grouped per-expert matmul [E,C,d]x[E,d,f]->[E,C,f]."""
    if _use_pallas():
        from .moe_gemm import moe_gemm_pallas
        interpret = jax.default_backend() != "tpu"
        return moe_gemm_pallas(x, w, interpret=interpret)
    return _ref.moe_gemm_ref(x, w)
