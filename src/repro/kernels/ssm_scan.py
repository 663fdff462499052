"""Selective-scan (Mamba) and generic linear-recurrence Pallas kernels.

TPU adaptation: the recurrence is sequential in time, so the grid puts the
time-block index minor-most (sequential on a TPU core) and carries the
state h [N, blk_d] (channels on lanes) in VMEM scratch across time
blocks. The channel dimension D is the parallel grid axis — each (batch,
d-block) recurs independently. This mirrors how the original CUDA kernel
splits channels over thread blocks, re-thought for VMEM residency: all
per-step tensors (x/dt tiles [blk_t, blk_d], B/C tiles [N, blk_t]) stay
in VMEM, and the inner fori walks blk_t steps with [N, blk_d] updates on
the VPU. Step i's rows are read and written through f32 scratch refs
with `pl.ds`: Mosaic has no lowering for a dynamic index into a value.

Oracles: kernels/ref.py::{selective_scan_ref, ssm_scan_ref}.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ------------------------------------------------------ selective scan
def _sel_scan_kernel(x_ref, dt_ref, a_ref, bt_ref, ct_ref, d_ref, h0_ref,
                     y_ref, hout_ref, h_scr, dt_scr, u_scr, y_scr, *,
                     blk_t: int, n: int):
    tb = pl.program_id(2)

    @pl.when(tb == 0)
    def _init():
        h_scr[...] = h0_ref[0]                       # [N, blk_d]

    a = -jnp.exp(a_ref[...].astype(jnp.float32))     # [N, blk_d]
    x = x_ref[0].astype(jnp.float32)                 # [blk_t, blk_d]
    dt = dt_ref[0].astype(jnp.float32)
    dt_scr[...] = dt
    u_scr[...] = dt * x
    bt = bt_ref[0].astype(jnp.float32)               # [N, blk_t]
    ct = ct_ref[0].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, blk_t), 1)

    def step(i, h):
        # the B/C columns for step i: an exact masked lane reduction
        sel = lane == i
        b_i = jnp.sum(jnp.where(sel, bt, 0.0), axis=1, keepdims=True)
        c_i = jnp.sum(jnp.where(sel, ct, 0.0), axis=1, keepdims=True)
        dt_i = dt_scr[pl.ds(i, 1), :]                # [1, blk_d]
        h = jnp.exp(dt_i * a) * h + b_i * u_scr[pl.ds(i, 1), :]
        y_scr[pl.ds(i, 1), :] = jnp.sum(h * c_i, axis=0, keepdims=True)
        return h

    h = jax.lax.fori_loop(0, blk_t, step, h_scr[...])
    h_scr[...] = h
    dvec = d_ref[...].astype(jnp.float32)            # [1, blk_d]
    y_ref[0] = (y_scr[...] + x * dvec).astype(y_ref.dtype)
    hout_ref[0] = h


def selective_scan_pallas(x: jax.Array, dt: jax.Array, a_log: jax.Array,
                          b: jax.Array, c: jax.Array, d: jax.Array,
                          h0: Optional[jax.Array] = None,
                          blk_t: int = 256, blk_d: int = 256,
                          interpret: bool = False
                          ) -> Tuple[jax.Array, jax.Array]:
    """x/dt [B,S,D]; a_log [D,N]; b/c [B,S,N]; d [D] -> (y, h_last).

    Inside the kernel the state is laid out [N, D] (channels on lanes),
    so a_log, b/c and the state are transposed here, outside it."""
    bsz, s, dd = x.shape
    n = a_log.shape[1]
    blk_t = min(blk_t, s)
    blk_d = min(blk_d, dd)
    assert s % blk_t == 0 and dd % blk_d == 0, (s, dd, blk_t, blk_d)
    if h0 is None:
        h0 = jnp.zeros((bsz, dd, n), jnp.float32)
    grid = (bsz, dd // blk_d, s // blk_t)
    kernel = functools.partial(_sel_scan_kernel, blk_t=blk_t, n=n)
    y, h_last = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_t, blk_d), lambda bi, di, ti: (bi, ti, di)),
            pl.BlockSpec((1, blk_t, blk_d), lambda bi, di, ti: (bi, ti, di)),
            pl.BlockSpec((n, blk_d), lambda bi, di, ti: (0, di)),
            pl.BlockSpec((1, n, blk_t), lambda bi, di, ti: (bi, 0, ti)),
            pl.BlockSpec((1, n, blk_t), lambda bi, di, ti: (bi, 0, ti)),
            pl.BlockSpec((1, blk_d), lambda bi, di, ti: (0, di)),
            pl.BlockSpec((1, n, blk_d), lambda bi, di, ti: (bi, 0, di)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_t, blk_d), lambda bi, di, ti: (bi, ti, di)),
            pl.BlockSpec((1, n, blk_d), lambda bi, di, ti: (bi, 0, di)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, dd), x.dtype),
            jax.ShapeDtypeStruct((bsz, n, dd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, blk_d), jnp.float32),
                        pltpu.VMEM((blk_t, blk_d), jnp.float32),
                        pltpu.VMEM((blk_t, blk_d), jnp.float32),
                        pltpu.VMEM((blk_t, blk_d), jnp.float32)],
        interpret=interpret,
    )(x, dt, a_log.T, jnp.swapaxes(b, 1, 2), jnp.swapaxes(c, 1, 2),
      d.reshape(1, dd), jnp.swapaxes(h0, 1, 2))
    return y, jnp.swapaxes(h_last, 1, 2)


# ------------------------------------------------- generic linear scan
def _lin_scan_kernel(a_ref, bx_ref, h0_ref, y_ref, h_scr, a_scr, bx_scr,
                     y_scr, *, blk_t: int):
    tb = pl.program_id(2)

    @pl.when(tb == 0)
    def _init():
        h_scr[...] = h0_ref[0]                       # [1, blk_d]

    a_scr[...] = a_ref[0].astype(jnp.float32)        # [blk_t, blk_d]
    bx_scr[...] = bx_ref[0].astype(jnp.float32)

    def step(i, h):
        h = a_scr[pl.ds(i, 1), :] * h + bx_scr[pl.ds(i, 1), :]
        y_scr[pl.ds(i, 1), :] = h
        return h

    h_scr[...] = jax.lax.fori_loop(0, blk_t, step, h_scr[...])
    y_ref[0] = y_scr[...].astype(y_ref.dtype)


def ssm_scan_pallas(a: jax.Array, bx: jax.Array,
                    h0: Optional[jax.Array] = None,
                    blk_t: int = 256, blk_d: int = 512,
                    interpret: bool = False) -> jax.Array:
    """Linear recurrence h_t = a_t*h_{t-1} + bx_t over axis 1.
    a/bx [B,S,D] -> h [B,S,D]."""
    bsz, s, dd = a.shape
    blk_t = min(blk_t, s)
    blk_d = min(blk_d, dd)
    assert s % blk_t == 0 and dd % blk_d == 0
    if h0 is None:
        h0 = jnp.zeros((bsz, dd), jnp.float32)
    grid = (bsz, dd // blk_d, s // blk_t)
    kernel = functools.partial(_lin_scan_kernel, blk_t=blk_t)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_t, blk_d), lambda bi, di, ti: (bi, ti, di)),
            pl.BlockSpec((1, blk_t, blk_d), lambda bi, di, ti: (bi, ti, di)),
            # [B, 1, D]: a (1, blk_d) tile over (B, D) breaks the TPU's
            # last-two-dims tiling rule once B > 1
            pl.BlockSpec((1, 1, blk_d), lambda bi, di, ti: (bi, 0, di)),
        ],
        out_specs=pl.BlockSpec((1, blk_t, blk_d),
                               lambda bi, di, ti: (bi, ti, di)),
        out_shape=jax.ShapeDtypeStruct((bsz, s, dd), bx.dtype),
        scratch_shapes=[pltpu.VMEM((1, blk_d), jnp.float32),
                        pltpu.VMEM((blk_t, blk_d), jnp.float32),
                        pltpu.VMEM((blk_t, blk_d), jnp.float32),
                        pltpu.VMEM((blk_t, blk_d), jnp.float32)],
        interpret=interpret,
    )(a, bx, h0.reshape(bsz, 1, dd))
