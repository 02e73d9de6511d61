"""Mamba selective-scan kernel (Pallas TPU).

Grid (B, d_inner/BD, S/C): channel blocks are parallel; the sequence-chunk
axis is innermost-sequential with the SSM state in fp32 VMEM scratch. The
discretized (dA, dBx) terms are formed *inside* the kernel from
(delta, A, B, C, x) — the (B, S, D, N) expansion that makes the pure-XLA
associative-scan path memory-hungry never touches HBM. This is the
TPU-native restatement of the CUDA selective-scan's SRAM strategy.

Layout: the state is kept (N, BD) — the small state dim on sublanes, the
channels on lanes — so each step's delta/x rows broadcast down it without
a transpose. The wrapper hands A, B and C in transposed ((N, D) and
(B, N, S)) so a step's B/C column is a lane-masked reduction of the
resident (N, C) chunk. The x/delta chunk is staged once in fp32 VMEM
scratch and y is gathered there too, so the per-step rows — read and
written with ``pl.ds`` at the traced step index — are whole fp32 rows
whatever the activation dtype (single rows of packed bf16 cannot be
sliced at a dynamic offset).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mamba_kernel(x_ref, delta_ref, at_ref, bt_ref, ct_ref, d_ref, y_ref,
                  h_scr, x_scr, dt_scr, y_scr, *, chunk: int):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x_scr[...] = x_ref[0].astype(jnp.float32)   # (C, BD)
    dt_scr[...] = delta_ref[0].astype(jnp.float32)
    at = at_ref[...].astype(jnp.float32)        # (N, BD)
    bt = bt_ref[0].astype(jnp.float32)          # (N, C)
    ct = ct_ref[0].astype(jnp.float32)          # (N, C)
    dd = d_ref[...].astype(jnp.float32)         # (1, BD)
    lane = jax.lax.broadcasted_iota(jnp.int32, bt.shape, 1)

    def step(t, h):
        x_t = x_scr[pl.ds(t, 1), :]                              # (1, BD)
        dt_t = dt_scr[pl.ds(t, 1), :]                            # (1, BD)
        now = lane == t
        b_t = jnp.sum(jnp.where(now, bt, 0.0), axis=1, keepdims=True)
        c_t = jnp.sum(jnp.where(now, ct, 0.0), axis=1, keepdims=True)
        h = jnp.exp(dt_t * at) * h + b_t * (dt_t * x_t)          # (N, BD)
        y_t = jnp.sum(h * c_t, axis=0, keepdims=True) + dd * x_t
        y_scr[pl.ds(t, 1), :] = y_t
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    y_ref[0] = y_scr[...].astype(y_ref.dtype)


def mamba_scan_fwd(x: jax.Array, delta: jax.Array, a: jax.Array,
                   b: jax.Array, c: jax.Array, d: jax.Array, *,
                   block_d: int = 256, chunk: int = 128,
                   interpret: bool = False) -> jax.Array:
    """x/delta: (B, S, D); a: (D, N); b/c: (B, S, N); d: (D,) -> y (B,S,D).

    On the chip ``chunk`` must be a multiple of 128 or all of S (it is the
    lane dim of the transposed B/C blocks)."""
    bsz, s, dim = x.shape
    n = a.shape[1]
    bd = min(block_d, dim)
    chunk = min(chunk, s)
    assert dim % bd == 0 and s % chunk == 0
    kernel = functools.partial(_mamba_kernel, chunk=chunk)
    xspec = pl.BlockSpec((1, chunk, bd), lambda i, j, t: (i, t, j))
    nspec = pl.BlockSpec((1, n, chunk), lambda i, j, t: (i, 0, t))
    return pl.pallas_call(
        kernel,
        grid=(bsz, dim // bd, s // chunk),
        in_specs=[
            xspec, xspec,
            pl.BlockSpec((n, bd), lambda i, j, t: (0, j)),
            nspec, nspec,
            pl.BlockSpec((1, bd), lambda i, j, t: (0, j)),
        ],
        out_specs=xspec,
        out_shape=jax.ShapeDtypeStruct((bsz, s, dim), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, bd), jnp.float32)]
        + [pltpu.VMEM((chunk, bd), jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, delta, a.T, jnp.swapaxes(b, 1, 2), jnp.swapaxes(c, 1, 2), d[None])
