"""Jitted wrapper for the WKV6 kernel: (B, S, H, dh) layout + custom VJP
(backward via the reference recurrence)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.rwkv6_wkv.kernel import wkv6_fwd
from repro.kernels.rwkv6_wkv.ref import wkv6_ref


def _fold(x):                                      # (B,S,H,d) -> (BH,S,d)
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def wkv6(r, k, v, lw, u, chunk: int = 64, interpret: bool = False):
    """r/k/v/lw: (B, S, H, dh); u: (H, dh)."""
    b, s, h, dh = r.shape
    u_full = jnp.broadcast_to(u[None], (b, h, dh)).reshape(b * h, dh)
    y = wkv6_fwd(_fold(r), _fold(k), _fold(v), _fold(lw), u_full,
                 chunk=chunk, interpret=interpret)
    return y.reshape(b, h, s, dh).transpose(0, 2, 1, 3)


def _fwd(r, k, v, lw, u, chunk, interpret):
    return wkv6(r, k, v, lw, u, chunk, interpret), (r, k, v, lw, u)


def _bwd(chunk, interpret, res, g):
    r, k, v, lw, u = res

    def ref(r, k, v, lw, u):
        b, s, h, dh = r.shape
        u_full = jnp.broadcast_to(u[None], (b, h, dh)).reshape(b * h, dh)
        y = wkv6_ref(_fold(r), _fold(k), _fold(v), _fold(lw), u_full)
        return y.reshape(b, h, s, dh).transpose(0, 2, 1, 3)

    _, vjp = jax.vjp(ref, r, k, v, lw, u)
    return vjp(g)


wkv6.defvjp(_fwd, _bwd)
