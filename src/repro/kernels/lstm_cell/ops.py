"""Jitted wrapper; whole-sequence runner built on the fused cell."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.lstm_cell.kernel import lstm_cell_fwd
from repro.kernels.lstm_cell.ref import lstm_cell_ref


def lstm_cell(xh, w, b, c, block_b: int = 128, block_h: int = 128,
              interpret: bool = False):
    return lstm_cell_fwd(xh, w, b, c, block_b=block_b, block_h=block_h,
                         interpret=interpret)


def lstm_sequence(xs, h0, c0, w, b, use_kernel: bool = True,
                  interpret: bool = False):
    """xs: (B, S, D); returns hidden states (B, S, H)."""
    cell = functools.partial(lstm_cell, interpret=interpret) if use_kernel \
        else lstm_cell_ref

    def step(carry, x):
        h, c = carry
        xh = jnp.concatenate([x, h], axis=-1)
        h, c = cell(xh, w, b, c)
        return (h, c), h

    (_, _), hs = jax.lax.scan(step, (h0, c0), jnp.moveaxis(xs, 1, 0))
    return jnp.moveaxis(hs, 0, 1)
