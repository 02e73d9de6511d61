"""Kernel microbenchmarks.

On this CPU container the Pallas kernels run in interpret mode (not
representative), so ``us_per_call`` times the jitted XLA reference path and
``derived`` carries the kernel's analytic TPU-side roofline time for the
same shape (197 TFLOP/s bf16 / 819 GB/s HBM)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit
from repro.perfmodel.machine import TPU_V5E


def flash_attention_bench(fast: bool) -> None:
    from repro.kernels.flash_attention.ref import attention_ref

    bh, s, dh = 8, 1024 if fast else 2048, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(ks[i], (bh, s, dh), jnp.bfloat16)
               for i in range(3))
    fn = jax.jit(lambda q, k, v: attention_ref(q, k, v, causal=True))
    us = timeit(fn, q, k, v)
    flops = 4 * bh * s * s * dh / 2          # causal
    hbm = 4 * bh * s * dh * 2
    t_tpu = TPU_V5E.step_time(flops, hbm, 0)
    emit("kernel_flash_attention_ref", us,
         f"tpu_roofline_us={t_tpu*1e6:.1f} flops={flops:.3g} shape=bh{bh}xS{s}xd{dh}")


def wkv6_bench(fast: bool) -> None:
    from repro.kernels.rwkv6_wkv.ref import wkv6_ref

    bh, s, dh = 8, 512 if fast else 1024, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    r, k, v = (jax.random.normal(ks[i], (bh, s, dh)) for i in range(3))
    lw = -jnp.exp(jnp.clip(jax.random.normal(ks[3], (bh, s, dh)), -8, 0))
    u = jax.random.normal(ks[4], (bh, dh))
    fn = jax.jit(wkv6_ref)
    us = timeit(fn, r, k, v, lw, u)
    # chunked kernel flops: intra (C x C) + inter state updates
    c = 64
    flops = bh * (s / c) * (2 * c * c * dh * 2 + 2 * c * dh * dh * 2)
    hbm = 5 * bh * s * dh * 4
    emit("kernel_rwkv6_wkv_ref", us,
         f"tpu_roofline_us={TPU_V5E.step_time(flops, hbm, 0)*1e6:.1f} "
         f"shape=bh{bh}xS{s}xd{dh}")


def mamba_bench(fast: bool) -> None:
    from repro.kernels.mamba_scan.ref import mamba_scan_ref

    b, s, d, n = 2, 256 if fast else 512, 512, 16
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    x = jax.random.normal(ks[0], (b, s, d))
    delta = jax.nn.softplus(jax.random.normal(ks[1], (b, s, d)) - 2)
    a = -jnp.exp(jax.random.normal(ks[2], (d, n)) * 0.3)
    bm, cm = (jax.random.normal(ks[i], (b, s, n)) for i in (3, 4))
    dd = jax.random.normal(ks[5], (d,))
    fn = jax.jit(mamba_scan_ref)
    us = timeit(fn, x, delta, a, bm, cm, dd)
    flops = 9 * b * s * d * n
    hbm = (2 * b * s * d + 2 * b * s * n) * 4
    emit("kernel_mamba_scan_ref", us,
         f"tpu_roofline_us={TPU_V5E.step_time(flops, hbm, 0)*1e6:.1f} "
         f"shape=B{b}xS{s}xD{d}xN{n}")


def lstm_bench(fast: bool) -> None:
    from repro.kernels.lstm_cell.ref import lstm_cell_ref

    b, d, h = 128, 512, 512
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    xh = jax.random.normal(ks[0], (b, d + h))
    w = jax.random.normal(ks[1], (4, d + h, h)) * 0.1
    bias = jax.random.normal(ks[2], (4, h)) * 0.1
    c = jax.random.normal(ks[3], (b, h))
    fn = jax.jit(lstm_cell_ref)
    us = timeit(fn, xh, w, bias, c)
    flops = 2 * b * (d + h) * 4 * h
    hbm = ((d + h) * 4 * h + b * (d + 2 * h)) * 4
    emit("kernel_lstm_cell_ref", us,
         f"tpu_roofline_us={TPU_V5E.step_time(flops, hbm, 0)*1e6:.1f} "
         f"shape=B{b}xD{d}xH{h}")


ALL = [flash_attention_bench, wkv6_bench, mamba_bench, lstm_bench]
