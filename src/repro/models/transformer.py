"""Pattern-based decoder-only LM covering dense / MoE / hybrid / SSM / VLM
families. Layers = `cfg.pattern` repeated `cfg.repeats` times; parameters
for each pattern position are stacked over repeats so the whole stack is a
single `lax.scan` (small HLO even at 94 layers), with jax.checkpoint remat
per period.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..parallel.collectives import constrain
from . import attention as attn
from . import ssm
from .config import BlockSpec, ModelConfig
from .layers import (apply_norm, embed_tokens, init_embed, init_mlp,
                     init_norm, apply_mlp, unembed)
from .moe import apply_moe, init_moe

Params = Dict[str, Any]


# ------------------------------------------------------------ block init
def _init_mixer(rng: jax.Array, cfg: ModelConfig, kind: str) -> Params:
    if kind in ("attn", "attn_local"):
        return attn.init_attention(rng, cfg)
    if kind == "mamba":
        return ssm.init_mamba(rng, cfg)
    if kind == "mlstm":
        return ssm.init_mlstm(rng, cfg)
    if kind == "slstm":
        return ssm.init_slstm(rng, cfg)
    raise ValueError(f"unknown mixer {kind!r}")


def init_block(rng: jax.Array, cfg: ModelConfig, bspec: BlockSpec) -> Params:
    k = jax.random.split(rng, 4)
    p: Params = {"norm_mixer": init_norm(cfg),
                 "mixer": _init_mixer(k[0], cfg, bspec.mixer)}
    if cfg.post_norm:
        p["post_norm_mixer"] = init_norm(cfg)
    if bspec.ffn == "mlp":
        p["norm_ffn"] = init_norm(cfg)
        p["ffn"] = init_mlp(k[1], cfg)
    elif bspec.ffn == "moe":
        p["norm_ffn"] = init_norm(cfg)
        p["ffn"] = init_moe(k[1], cfg)
    if cfg.post_norm and bspec.ffn != "none":
        p["post_norm_ffn"] = init_norm(cfg)
    return p


def init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Full parameter pytree; per-position leaves stacked over repeats."""
    k_embed, k_layers, k_final = jax.random.split(rng, 3)
    layers = []
    for pos, bspec in enumerate(cfg.pattern):
        keys = jax.random.split(jax.random.fold_in(k_layers, pos),
                                cfg.repeats)
        stacked = jax.vmap(lambda kk: init_block(kk, cfg, bspec))(keys)
        layers.append(stacked)
    return {"embed": init_embed(k_embed, cfg),
            "layers": tuple(layers),
            "final_norm": init_norm(cfg)}


# ------------------------------------------------------------ train path
def apply_block_train(cfg: ModelConfig, bspec: BlockSpec, p: Params,
                      x: jax.Array, aux: jax.Array
                      ) -> Tuple[jax.Array, jax.Array]:
    h = apply_norm(cfg, p["norm_mixer"], x)
    kind = bspec.mixer
    if kind in ("attn", "attn_local"):
        h = attn.attention_train(cfg, p["mixer"], h,
                                 local=(kind == "attn_local"))
    elif kind == "mamba":
        h = ssm.mamba_train(cfg, p["mixer"], h)
    elif kind == "mlstm":
        h = ssm.mlstm_train(cfg, p["mixer"], h)
    else:
        h = ssm.slstm_train(cfg, p["mixer"], h)
    if cfg.post_norm:
        h = apply_norm(cfg, p["post_norm_mixer"], h)
    x = x + h
    if bspec.ffn != "none":
        h = apply_norm(cfg, p["norm_ffn"], x)
        if bspec.ffn == "moe":
            h, a = apply_moe(cfg, p["ffn"], h)
            aux = aux + a
        else:
            h = apply_mlp(cfg, p["ffn"], h)
        if cfg.post_norm:
            h = apply_norm(cfg, p["post_norm_ffn"], h)
        x = x + h
    return x, aux


def forward(cfg: ModelConfig, params: Params, tokens: jax.Array,
            embeds: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """tokens [B,S] (or `embeds` [B,S,d] from a modality frontend stub)
    -> (logits [B,S,V], moe aux loss)."""
    x = embeds if embeds is not None else \
        embed_tokens(cfg, params["embed"], tokens)
    x = constrain(x, "dp", None, None)
    aux0 = jnp.zeros((), jnp.float32)

    @jax.checkpoint
    def period_fn(carry, layer_slice):
        x, aux = carry
        for pos, bspec in enumerate(cfg.pattern):
            x, aux = apply_block_train(cfg, bspec, layer_slice[pos], x, aux)
            x = constrain(x, "dp", None, None)
        return (x, aux), None

    (x, aux), _ = jax.lax.scan(period_fn, (x, aux0), params["layers"])
    x = apply_norm(cfg, params["final_norm"], x)
    logits = unembed(cfg, params["embed"], x)
    return logits, aux


# ----------------------------------------------------------- decode path
def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    """Per pattern position, stacked over repeats (so decode also scans)."""
    caches = []
    for bspec in cfg.pattern:
        if bspec.mixer in ("attn", "attn_local"):
            one = attn.init_kv_cache(cfg, batch, max_len)
        elif bspec.mixer == "mamba":
            one = ssm.init_mamba_state(cfg, batch)
        elif bspec.mixer == "mlstm":
            one = ssm.init_mlstm_state(cfg, batch)
        else:
            one = ssm.init_slstm_state(cfg, batch)
        caches.append(jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.repeats,) + a.shape).copy(),
            one))
    return tuple(caches)


def apply_block_decode(cfg: ModelConfig, bspec: BlockSpec, p: Params,
                       x: jax.Array, cache: Params, pos: jax.Array
                       ) -> Tuple[jax.Array, Params]:
    h = apply_norm(cfg, p["norm_mixer"], x)
    kind = bspec.mixer
    if kind in ("attn", "attn_local"):
        h, cache = attn.attention_decode(cfg, p["mixer"], h, cache, pos,
                                         local=(kind == "attn_local"))
    elif kind == "mamba":
        h, cache = ssm.mamba_decode(cfg, p["mixer"], h, cache)
    elif kind == "mlstm":
        h, cache = ssm.mlstm_decode(cfg, p["mixer"], h, cache)
    else:
        h, cache = ssm.slstm_decode(cfg, p["mixer"], h, cache)
    if cfg.post_norm:
        h = apply_norm(cfg, p["post_norm_mixer"], h)
    x = x + h
    if bspec.ffn != "none":
        h = apply_norm(cfg, p["norm_ffn"], x)
        if bspec.ffn == "moe":
            h, _ = apply_moe(cfg, p["ffn"], h)
        else:
            h = apply_mlp(cfg, p["ffn"], h)
        if cfg.post_norm:
            h = apply_norm(cfg, p["post_norm_ffn"], h)
        x = x + h
    return x, cache


def decode_step(cfg: ModelConfig, params: Params, cache: Params,
                tokens: jax.Array, pos: jax.Array
                ) -> Tuple[jax.Array, Params]:
    """One decode step. tokens [B]; pos scalar int32 (current position).
    Returns (logits [B,V], new cache)."""
    x = embed_tokens(cfg, params["embed"], tokens[:, None])

    def step_fn(x, slices):
        layer_slice, cache_slice = slices
        new_cache = []
        for p_, bspec in enumerate(cfg.pattern):
            x, c = apply_block_decode(cfg, bspec, layer_slice[p_], x,
                                      cache_slice[p_], pos)
            new_cache.append(c)
        return x, tuple(new_cache)

    x, new_cache = jax.lax.scan(step_fn, x, (params["layers"], cache))
    x = apply_norm(cfg, params["final_norm"], x)
    logits = unembed(cfg, params["embed"], x)
    return logits[:, 0], new_cache


def chunk_prefill_exact(cfg: ModelConfig) -> bool:
    """Whether `prefill_chunk` gives what one-token decode gives: every
    mixer is attention (a recurrent state would need a chunked scan) and
    every FFN dense (MoE capacity drops tokens in a chunk, never in a
    one-token step)."""
    return all(b.mixer in ("attn", "attn_local") and b.ffn in ("mlp", "none")
               for b in cfg.pattern)


def apply_block_prefill(cfg: ModelConfig, bspec: BlockSpec, p: Params,
                        x: jax.Array, row: Params, start: jax.Array
                        ) -> Tuple[jax.Array, Params]:
    h = apply_norm(cfg, p["norm_mixer"], x)
    h, kv = attn.attention_prefill(cfg, p["mixer"], h, row, start,
                                   local=(bspec.mixer == "attn_local"))
    if cfg.post_norm:
        h = apply_norm(cfg, p["post_norm_mixer"], h)
    x = x + h
    if bspec.ffn != "none":
        h = apply_mlp(cfg, p["ffn"], apply_norm(cfg, p["norm_ffn"], x))
        if cfg.post_norm:
            h = apply_norm(cfg, p["post_norm_ffn"], h)
        x = x + h
    return x, kv


def prefill_chunk(cfg: ModelConfig, params: Params, cache: Params,
                  tokens: jax.Array, slot: jax.Array, start: jax.Array,
                  n_valid: jax.Array) -> Tuple[jax.Array, Params]:
    """Run C prompt tokens of one slot through the model and write their
    K/V into the cache. tokens [C] sit at positions start..start+C-1, the
    first n_valid of them real; slot, start and n_valid are int32
    scalars, so one program serves every slot, offset and length. The
    slot's rows below `start` must hold the prompt's earlier chunks.
    Returns (the greedy token after position start+n_valid-1, cache).
    Only for configs with `chunk_prefill_exact`."""
    c = tokens.shape[0]
    x = embed_tokens(cfg, params["embed"], tokens[None])
    rows = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, slot, 1, keepdims=False),
        cache)                                       # [R, L, nkv, hd]

    def step_fn(x, slices):
        layer_slice, row_slice = slices
        kvs = []
        for p_, bspec in enumerate(cfg.pattern):
            x, kv = apply_block_prefill(cfg, bspec, layer_slice[p_], x,
                                        row_slice[p_], start)
            kvs.append(kv)
        return x, tuple(kvs)

    x, kvs = jax.lax.scan(step_fn, x, (params["layers"], rows))
    # kvs: [R, C, nkv, hd], the rows' C positions from min(start, L - C)
    at = jnp.minimum(start, cache[0]["k"].shape[2] - c)
    zero = jnp.zeros((), jnp.int32)
    cache = jax.tree.map(
        lambda a, new: jax.lax.dynamic_update_slice(
            a, new[:, None].astype(a.dtype), (zero, slot, at, zero, zero)),
        cache, kvs)
    last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
    logits = unembed(cfg, params["embed"],
                     apply_norm(cfg, params["final_norm"], last))
    # over the real vocabulary only, as the decode step's argmax
    tok = jnp.argmax(logits[0, 0, :cfg.vocab_size]).astype(jnp.int32)
    return tok, cache


def prefill(cfg: ModelConfig, params: Params, tokens: jax.Array
            ) -> Tuple[jax.Array, jax.Array]:
    """Prefill = teacher-forced forward over the prompt; returns logits.
    (Cache-filling prefill is `prefill_chunk`; for the prefill_32k
    dry-run cell the compute-equivalent forward is lowered.)"""
    return forward(cfg, params, tokens)
