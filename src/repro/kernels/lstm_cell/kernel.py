"""Fused LSTM cell kernel (Pallas TPU) — the paper's GNMT hot loop.

One timestep over the batch: z = [x; h] @ W + b followed by the four-gate
state update, fused so gate preactivations never round-trip to HBM (the
MIOpen kernels the paper profiles do the same on GPU).

Weights are laid out gate-major, (4, D+H, H), so a hidden-column block
carries all four gates for its units as four (D+H, BH) slabs: each gate is
one plain matmul and no in-register reshape splits a fused gate axis (the
TPU compiler rejects such 3-D shape casts). Grid: (B/BB, H/BH); the
contraction dimension stays resident in VMEM (recurrent weights are the
reuse case persistent-RNN papers optimize).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lstm_kernel(xh_ref, w_ref, b_ref, c_ref, h_out_ref, c_out_ref):
    xh = xh_ref[...]                                 # (BB, D+H)

    def gate(g):                                     # (BB, BH) preactivation
        return jnp.dot(xh, w_ref[g], preferred_element_type=jnp.float32) \
            + b_ref[pl.ds(g, 1), :].astype(jnp.float32)

    c = c_ref[...].astype(jnp.float32)
    c_new = jax.nn.sigmoid(gate(1) + 1.0) * c \
        + jax.nn.sigmoid(gate(0)) * jnp.tanh(gate(2))
    h_new = jax.nn.sigmoid(gate(3)) * jnp.tanh(c_new)
    h_out_ref[...] = h_new.astype(h_out_ref.dtype)
    c_out_ref[...] = c_new.astype(c_out_ref.dtype)


def lstm_cell_fwd(xh: jax.Array, w: jax.Array, b: jax.Array, c: jax.Array, *,
                  block_b: int = 128, block_h: int = 128,
                  interpret: bool = False):
    """xh: (B, D+H) concat of input and previous hidden; w: (4, D+H, H)
    gate-major (i, f, g, o); b: (4, H); c: (B, H). Returns (h_new, c_new)."""
    bsz, dh_in = xh.shape
    h = w.shape[2]
    bb = min(block_b, bsz)
    bh = min(block_h, h)
    assert bsz % bb == 0 and h % bh == 0
    return pl.pallas_call(
        _lstm_kernel,
        grid=(bsz // bb, h // bh),
        in_specs=[
            pl.BlockSpec((bb, dh_in), lambda i, j: (i, 0)),
            pl.BlockSpec((4, dh_in, bh), lambda i, j: (0, 0, j)),
            pl.BlockSpec((4, bh), lambda i, j: (0, j)),
            pl.BlockSpec((bb, bh), lambda i, j: (i, j)),
        ],
        out_specs=[pl.BlockSpec((bb, bh), lambda i, j: (i, j)),
                   pl.BlockSpec((bb, bh), lambda i, j: (i, j))],
        out_shape=[jax.ShapeDtypeStruct((bsz, h), xh.dtype),
                   jax.ShapeDtypeStruct((bsz, h), xh.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(xh, w, b, c)
