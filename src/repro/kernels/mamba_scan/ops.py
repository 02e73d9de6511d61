"""Jitted wrapper + custom VJP (backward via reference scan)."""
from __future__ import annotations

import functools

import jax

from repro.kernels.mamba_scan.kernel import mamba_scan_fwd
from repro.kernels.mamba_scan.ref import mamba_scan_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def mamba_scan(x, delta, a, b, c, d, block_d: int = 256, chunk: int = 128,
               interpret: bool = False):
    return mamba_scan_fwd(x, delta, a, b, c, d, block_d=block_d, chunk=chunk,
                          interpret=interpret)


def _fwd(x, delta, a, b, c, d, block_d, chunk, interpret):
    return mamba_scan(x, delta, a, b, c, d, block_d, chunk, interpret), \
        (x, delta, a, b, c, d)


def _bwd(block_d, chunk, interpret, res, g):
    _, vjp = jax.vjp(mamba_scan_ref, *res)
    return vjp(g)


mamba_scan.defvjp(_fwd, _bwd)
