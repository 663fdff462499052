"""Plain reference of the blocked matrix product C = A @ B.

The configuration states float32 blocks multiplied at the chip's default
matrix precision: on a TPU that rounds both operands of a float32
product to bfloat16 and accumulates in float32. The reference computes
exactly that, plainly: A and B made again from the seed by the
benchmark's generator, rounded to bfloat16, widened back to float32 and
multiplied at `Precision.HIGHEST` (a product of two bfloat16 values is
exact in float32, so only the order of the float32 sums can differ from
the program's). It imports nothing of the program.

Each C block is judged by its relative error, rms(C - R) / rms(R); the
number compared is the largest over every block of every graph checked.

The control is the same product one precision step down: blocks stored
and accumulated in bfloat16 (each task's C += A @ B rounded to bfloat16),
in the program's order of k.
"""
from __future__ import annotations

from typing import List, Sequence

from benchlib.weights import matrix_blocks


def _rounded(blocks, nb, bs):
    import jax.numpy as jnp
    rows = [jnp.concatenate(blocks[i * nb:(i + 1) * nb], axis=1)
            for i in range(nb)]
    return jnp.concatenate(rows, axis=0).astype(jnp.bfloat16)


def check_blocks(c: dict, seed: int, n: int, bs: int,
                 answers: Sequence[List], control: bool = False) -> dict:
    """`answers`: each a list of the (n/bs)^2 C blocks, row-major."""
    import jax
    import jax.numpy as jnp
    nb = n // bs
    a = _rounded(matrix_blocks(n, bs, seed, 1), nb, bs)
    b = _rounded(matrix_blocks(n, bs, seed, 2), nb, bs)
    hi = jax.lax.Precision.HIGHEST

    def errs(got, want):
        """Relative error of each [bs, bs] block of two [nb, bs, bs]."""
        return jnp.sqrt(jnp.mean((got - want) ** 2, axis=(1, 2))
                        / jnp.mean(want ** 2, axis=(1, 2)))

    def blocks_of(row):
        return row.reshape(bs, nb, bs).transpose(1, 0, 2)

    @jax.jit
    def row_errs(a, b, i, got_rows):
        a_row = jax.lax.dynamic_slice_in_dim(a, i * bs, bs)
        want = blocks_of(jnp.dot(a_row.astype(jnp.float32),
                                 b.astype(jnp.float32), precision=hi))
        return jnp.stack([errs(jnp.stack(g), want) for g in got_rows])

    @jax.jit
    def row_ctrl_errs(a, b, i):
        a_row = jax.lax.dynamic_slice_in_dim(a, i * bs, bs)
        want = blocks_of(jnp.dot(a_row.astype(jnp.float32),
                                 b.astype(jnp.float32), precision=hi))
        acc = jnp.zeros((bs, n), jnp.bfloat16)
        for k in range(nb):
            part = jnp.dot(a_row[:, k * bs:(k + 1) * bs],
                           b[k * bs:(k + 1) * bs],
                           preferred_element_type=jnp.float32)
            acc = (acc.astype(jnp.float32) + part).astype(jnp.bfloat16)
        return errs(blocks_of(acc.astype(jnp.float32)), want)

    worst, cworst = 0.0, 0.0
    for i in range(nb):
        got_rows = [blocks[i * nb:(i + 1) * nb] for blocks in answers]
        worst = max(worst, float(jnp.max(row_errs(a, b, i, got_rows))))
        if control:
            cworst = max(cworst, float(jnp.max(row_ctrl_errs(a, b, i))))
    out = {"block_err_max": worst}
    if control:
        out["control_block_err_max"] = cworst
    return out
