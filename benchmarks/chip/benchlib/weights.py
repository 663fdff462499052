"""Weights and matrices made on the device from the seed.

One jitted call makes every array of a configuration. The program gets
them in the type it serves; the plain reference makes the same arrays
again from the same seed after the program's state is freed, so it takes
nothing the program made.
"""
from __future__ import annotations

from typing import Dict, Tuple

from .flops import Decoder


def key_for(seed: int, stream: int):
    import jax
    key = jax.random.key(int(seed) & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, int(seed) >> 32),
                              stream)


def decoder_shapes(m: Decoder) -> Dict[str, Tuple[tuple, float, float]]:
    """name -> (shape, std, mean) of a dense decoder's canonical weights,
    layers stacked on the first axis. Biases and norm scales are random
    too, so that every path of the block carries signal."""
    L, d, f = m.layers, m.d_model, m.d_ff
    q, kv = m.heads * m.head_dim, m.kv_heads * m.head_dim
    return {
        "embed": ((m.vocab, d), 0.02, 0.0),
        "ln1": ((L, d), 0.1, 1.0),
        "wq": ((L, d, q), d ** -0.5, 0.0),
        "bq": ((L, q), 0.1, 0.0),
        "wk": ((L, d, kv), d ** -0.5, 0.0),
        "bk": ((L, kv), 0.1, 0.0),
        "wv": ((L, d, kv), d ** -0.5, 0.0),
        "bv": ((L, kv), 0.1, 0.0),
        "wo": ((L, q, d), q ** -0.5, 0.0),
        "ln2": ((L, d), 0.1, 1.0),
        "w_gate": ((L, d, f), d ** -0.5, 0.0),
        "w_up": ((L, d, f), d ** -0.5, 0.0),
        "w_down": ((L, f, d), f ** -0.5, 0.0),
        "final_norm": ((d,), 0.1, 1.0),
    }


def _gen(shapes, dtype, key):
    import jax
    import jax.numpy as jnp
    out = {}
    for i, name in enumerate(sorted(shapes)):
        shape, std, mean = shapes[name]
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out[name] = (mean + std * x).astype(dtype)
    return out


def decoder_weights(m: Decoder, seed: int, dtype: str = "bfloat16",
                    convert=None):
    """Canonical weights (see decoder_shapes) in `dtype`; with `convert`,
    what convert(weights) returns, made in the same jitted call."""
    import jax
    shapes = decoder_shapes(m)

    def make(key):
        w = _gen(shapes, dtype, key)
        return convert(w) if convert is not None else w
    return jax.jit(make)(key_for(seed, 0))


def matrix_blocks(n: int, bs: int, seed: int, stream: int):
    """An n x n float32 standard-normal matrix as a list of its
    (n/bs)^2 blocks in row-major block order, made in one jitted call."""
    import jax
    import jax.numpy as jnp
    nb = n // bs

    @jax.jit
    def make(key):
        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
            jnp.arange(nb * nb))
        x = jax.vmap(lambda k: jax.random.normal(k, (bs, bs),
                                                 jnp.float32))(keys)
        return [x[i] for i in range(nb * nb)]
    return make(key_for(seed, stream))


def leaf_norms(w: dict) -> dict:
    """L2 norm of each leaf, layer by layer for the stacked ones:
    {"wq.0": ..., "embed": ...} (traceable)."""
    import jax.numpy as jnp
    out = {}
    for k, a in w.items():
        a = a.astype(jnp.float32)
        if k in ("embed", "final_norm"):
            out[k] = jnp.sqrt(jnp.sum(a * a))
        else:
            per = jnp.sqrt(jnp.sum(a * a, axis=tuple(range(1, a.ndim))))
            for i in range(a.shape[0]):
                out[f"{k}.{i}"] = per[i]
    return out
