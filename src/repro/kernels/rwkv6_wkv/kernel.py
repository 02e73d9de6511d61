"""RWKV-6 WKV kernel (Pallas TPU): chunked linear attention with
data-dependent per-channel decay.

Grid is (B*H, S/C) with the chunk axis innermost-sequential ("arbitrary"):
the per-head (dh x dh) state lives in fp32 VMEM scratch and is carried
across chunk steps — the TPU-native replacement for the CUDA wkv kernels
(DESIGN.md §3). Within a chunk everything is dense (C x C) MXU work:

  y_i = r~_i @ S_in + sum_{j<i} (r~_i . k~_j) v_j + (r_i . u k_i) v_i
  S_out = exp(total) * S_in + (k * exp(total - cs))^T V

with r~ = r * exp(cs_{i-1}), k~ = k * exp(-cs_j) (log-decays clamped to
[-1, 0) as in the model code, so exp(-cs) fits fp32 for C <= 64). The
inclusive prefix sum cs is a matmul with a lower-triangular ones mask (the
TPU lowering has no cumsum). Every matmul runs at fp32 precision: the
exp(+-cs) factors reach e^64 and cancel only if the products are exact to
fp32, which one bf16 MXU pass is not.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _wkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, y_ref, s_scr, *,
                 chunk: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0].astype(jnp.float32)                # (C, dh)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)                # (1, dh)

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    cs = _dot(jnp.where(ii >= jj, 1.0, 0.0), lw, ((1,), (0,)))  # inclusive
    total = cs[chunk - 1:chunk]                     # (1, dh)
    rq = r * jnp.exp(cs - lw)                       # r~ (decay to i-1)
    kk = k * jnp.exp(-cs)                           # k~
    att = jnp.where(ii > jj, _dot(rq, kk, ((1,), (1,))), 0.0)
    diag = jnp.sum(r * (u * k), axis=1, keepdims=True)   # (C, 1)
    y = (_dot(att, v, ((1,), (0,))) + diag * v
         + _dot(rq, s_scr[...], ((1,), (0,))))
    y_ref[0] = y.astype(y_ref.dtype)

    kdec = k * jnp.exp(total - cs)
    s_scr[...] = (s_scr[...] * jnp.exp(total).T
                  + _dot(kdec, v, ((0,), (0,))))


def wkv6_fwd(r: jax.Array, k: jax.Array, v: jax.Array, lw: jax.Array,
             u: jax.Array, *, chunk: int = 64,
             interpret: bool = False) -> jax.Array:
    """r/k/v/lw: (BH, S, dh) fp32-ish; u: (BH, dh). Returns y: (BH, S, dh)."""
    bh, s, dh = r.shape
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    n = s // chunk
    kernel = functools.partial(_wkv6_kernel, chunk=chunk)
    spec = pl.BlockSpec((1, chunk, dh), lambda b, j: (b, j, 0))
    return pl.pallas_call(
        kernel,
        grid=(bh, n),
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec((1, 1, dh), lambda b, j: (b, 0, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((bh, s, dh), r.dtype),
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, lw, u[:, None, :])
