"""Weights and matrices made on the device from the seed.

One jitted call makes every array of a configuration. The program gets
them in the type it serves; the plain reference makes the same arrays
again from the same seed after the program's state is freed, so it takes
nothing the program made.
"""
from __future__ import annotations

from typing import Dict, Tuple


def key_for(seed: int, stream: int):
    import jax
    key = jax.random.key(int(seed) & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, int(seed) >> 32),
                              stream)


def _gen(shapes, dtype, key):
    import jax
    import jax.numpy as jnp
    out = {}
    for i, name in enumerate(sorted(shapes)):
        shape, std, mean = shapes[name]
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out[name] = (mean + std * x).astype(dtype)
    return out


def generate(shapes: Dict[str, Tuple[tuple, float, float]], seed: int,
             dtype: str = "bfloat16", convert=None):
    """Arrays by name from `shapes` (name -> (shape, std, mean)), normal
    draws in `dtype`, made from the seed in one jitted call: leaf i of
    the sorted names draws from fold_in(key_for(seed, 0), i). With
    `convert`, what convert(arrays) returns, made in the same call."""
    import jax

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


def leaf_norms(w: dict, unstacked) -> dict:
    """L2 norm of each leaf, layer by layer for those stacked on the first
    axis (all but the names in `unstacked`): {"wq.0": ..., "embed": ...}
    (traceable)."""
    import jax.numpy as jnp
    out = {}
    for k, a in w.items():
        a = a.astype(jnp.float32)
        if k in unstacked:
            out[k] = jnp.sqrt(jnp.sum(a * a))
        else:
            per = jnp.sqrt(jnp.sum(a * a, axis=tuple(range(1, a.ndim))))
            for i in range(a.shape[0]):
                out[f"{k}.{i}"] = per[i]
    return out
