"""Jitted public wrapper: (B, S, H, dh) layout, GQA, custom VJP.

Forward runs the Pallas kernel (``interpret=True`` runs its body in the
Pallas interpreter, for hosts without a TPU); backward falls back to the
jnp reference (correct everywhere; a fused backward kernel is the natural
next step)."""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ref import attention_ref


def _fold(x: jax.Array) -> jax.Array:                 # (B,S,H,d) -> (BH,S,d)
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _unfold(x: jax.Array, b: int) -> jax.Array:
    bh, s, d = x.shape
    return x.reshape(b, bh // b, s, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q: (B, Sq, Hq, dh); k/v: (B, Skv, Hkv, dh); returns (B, Sq, Hq, dh)."""
    out = flash_attention_fwd(_fold(q), _fold(k), _fold(v), causal=causal,
                              block_q=block_q, block_k=block_k,
                              interpret=interpret)
    return _unfold(out, q.shape[0])


def _fwd(q, k, v, causal, block_q, block_k, interpret):
    return flash_attention(q, k, v, causal, block_q, block_k,
                           interpret), (q, k, v)


def _bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v = res

    def ref(q, k, v):
        b = q.shape[0]
        return _unfold(attention_ref(_fold(q), _fold(k), _fold(v),
                                     causal=causal), b)

    _, vjp = jax.vjp(ref, q, k, v)
    return vjp(g)


flash_attention.defvjp(_fwd, _bwd)
