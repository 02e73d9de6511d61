"""Flash attention forward kernel (Pallas TPU).

Online-softmax over KV blocks with VMEM accumulators. Grid is
(batch*q_heads, Sq/BQ, Skv/BK); the KV dimension is the innermost
("arbitrary") axis so the fp32 scratch accumulators persist across KV steps
for a fixed (bh, q-block). Causal blocks entirely above the diagonal are
skipped via ``pl.when`` — the waste the pure-XLA chunked path cannot avoid
(DESIGN.md §6 hillclimb notes). GQA is folded into the index maps: the KV
block index map points query head h at KV head h // group.

Block shapes are MXU-aligned (BQ, BK multiples of 128 when Sq/Skv allow;
head_dim is the lane dimension).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                      scale: float, causal: bool, bq: int, bk: int,
                      n_k: int):
    i = pl.program_id(1)          # q block
    j = pl.program_id(2)          # kv block

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = i * bq
    k_start = j * bk
    run = True
    if causal:
        run = k_start <= q_start + bq - 1

    @pl.when(run if causal else True)
    def _body():
        q = q_ref[0].astype(jnp.float32)            # (BQ, dh)
        k = k_ref[0].astype(jnp.float32)            # (BK, dh)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == n_k - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, block_q: int = 128,
                        block_k: int = 128,
                        interpret: bool = False) -> jax.Array:
    """q: (BH, Sq, dh); k/v: (BHkv, Skv, dh) with BH % BHkv == 0."""
    bh, sq, dh = q.shape
    bhkv, skv, _ = k.shape
    group = bh // bhkv
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)
    n_q, n_k = sq // bq, skv // bk
    scale = 1.0 / math.sqrt(dh)

    kernel = functools.partial(_flash_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j, g=group: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
