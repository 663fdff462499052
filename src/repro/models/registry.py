"""Uniform model API over the decoder-only family and the enc-dec family,
plus `input_specs` — the ShapeDtypeStruct stand-ins every dry-run cell
lowers against (weak-type-correct, shardable, no device allocation)."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import encdec, transformer
from .config import ModelConfig, ShapeSpec

Params = Dict[str, Any]


@dataclass(frozen=True)
class ModelAPI:
    cfg: ModelConfig
    init_params: Callable[[jax.Array], Params]
    forward: Callable[..., Tuple[jax.Array, jax.Array]]
    decode_step: Callable[..., Tuple[jax.Array, Params]]
    init_cache: Callable[[int, int], Params]
    # (params, cache, tokens [C], slot, start, n_valid) -> (token, cache);
    # None where a chunk would not give what one-token decode gives
    prefill_chunk: Optional[Callable[..., Tuple[jax.Array, Params]]] = None


def get_model(cfg: ModelConfig) -> ModelAPI:
    if cfg.is_encoder_decoder:
        return ModelAPI(
            cfg=cfg,
            init_params=lambda rng: encdec.init_params(rng, cfg),
            forward=lambda params, batch: encdec.forward(
                cfg, params, batch["tokens"], frames=batch.get("frames")),
            decode_step=lambda params, cache, tokens, pos:
                encdec.decode_step(cfg, params, cache, tokens, pos),
            init_cache=lambda batch, max_len:
                encdec.init_cache(cfg, batch, max_len),
        )
    return ModelAPI(
        cfg=cfg,
        init_params=lambda rng: transformer.init_params(rng, cfg),
        forward=lambda params, batch: transformer.forward(
            cfg, params, batch["tokens"], embeds=batch.get("embeds")),
        decode_step=lambda params, cache, tokens, pos:
            transformer.decode_step(cfg, params, cache, tokens, pos),
        init_cache=lambda batch, max_len:
            transformer.init_cache(cfg, batch, max_len),
        prefill_chunk=(
            (lambda params, cache, tokens, slot, start, n_valid:
             transformer.prefill_chunk(cfg, params, cache, tokens, slot,
                                       start, n_valid))
            if transformer.chunk_prefill_exact(cfg) else None),
    )


def param_specs(cfg: ModelConfig) -> Params:
    """ShapeDtypeStruct pytree of the parameters (no allocation)."""
    model = get_model(cfg)
    return jax.eval_shape(model.init_params, jax.random.key(0))


def cache_specs(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    model = get_model(cfg)
    return jax.eval_shape(lambda: model.init_cache(batch, max_len))


def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """Stand-ins for every model input of the given shape cell.

    train/prefill -> {tokens, labels[, frames]}
    decode        -> {tokens [B], pos scalar, cache pytree}
    """
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind in ("train", "prefill"):
        specs: Dict[str, Any] = {
            "tokens": jax.ShapeDtypeStruct((b, s), i32),
            "labels": jax.ShapeDtypeStruct((b, s), i32),
        }
        if cfg.is_encoder_decoder:
            specs["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.encoder_seq, cfg.d_model), cfg.jnp_dtype)
        return specs
    # decode: one new token against a seq_len KV cache
    return {
        "tokens": jax.ShapeDtypeStruct((b,), i32),
        "pos": jax.ShapeDtypeStruct((), i32),
        "cache": cache_specs(cfg, b, s),
    }
