"""Offline greedy serving: cache-filling prefill by a decode scan over
the prompt, then greedy decode. The tests' oracle for the engine (whose
own prompt paths are `transformer.prefill_chunk` and, for recurrent and
MoE models, teacher-forcing through its decode step)."""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

from ..models.registry import ModelAPI


def prefill_into_cache(model: ModelAPI, params: Any, cache: Any,
                       prompt: jax.Array) -> Tuple[jax.Array, Any]:
    """Teacher-force the prompt through the decode path to fill the cache.
    prompt [B, P] -> (logits of last position [B, V], cache)."""
    p_len = prompt.shape[1]

    def body(carry, t):
        cache, _ = carry
        logits, cache = model.decode_step(params, cache, prompt[:, t], t)
        return (cache, logits.astype(jnp.float32)), None

    (cache, logits), _ = jax.lax.scan(
        body, (cache, jnp.zeros((prompt.shape[0],
                                 _vocab(model, params)), jnp.float32)),
        jnp.arange(p_len))
    return logits, cache


def _vocab(model: ModelAPI, params: Any) -> int:
    emb = params["embed"]["embedding"]
    return emb.shape[0]


def greedy_decode(model: ModelAPI, params: Any, prompt: jax.Array,
                  max_new: int, max_len: int) -> jax.Array:
    """prompt [B,P] -> generated tokens [B,max_new] (greedy)."""
    b, p_len = prompt.shape
    cache = model.init_cache(b, max_len)
    logits, cache = prefill_into_cache(model, params, cache, prompt)
    tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def body(carry, t):
        cache, tok = carry
        logits, cache = model.decode_step(params, cache, tok, p_len + t)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (cache, nxt), tok

    (_, _), toks = jax.lax.scan(body, (cache, tok0), jnp.arange(max_new))
    return jnp.moveaxis(toks, 0, 1)
