"""Per-kernel shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.lstm_cell.kernel import lstm_cell_fwd
from repro.kernels.lstm_cell.ref import lstm_cell_ref
from repro.kernels.mamba_scan.kernel import mamba_scan_fwd
from repro.kernels.mamba_scan.ref import mamba_scan_ref
from repro.kernels.rwkv6_wkv.kernel import wkv6_fwd
from repro.kernels.rwkv6_wkv.ref import wkv6_ref

RNG = jax.random.PRNGKey(0)


@pytest.mark.parametrize("bh,bhkv,sq,skv,dh,causal", [
    (2, 2, 128, 128, 64, True),
    (4, 2, 256, 256, 64, True),
    (4, 1, 128, 256, 128, False),
    (8, 4, 384, 384, 64, True),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(bh, bhkv, sq, skv, dh, causal, dtype):
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (bh, sq, dh), dtype)
    k = jax.random.normal(ks[1], (bhkv, skv, dh), dtype)
    v = jax.random.normal(ks[2], (bhkv, skv, dh), dtype)
    out = flash_attention_fwd(q, k, v, causal=causal, block_q=128,
                              block_k=128, interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    tol = 2e-3 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("bh,s,dh,chunk", [
    (2, 128, 64, 32), (4, 256, 64, 64), (2, 64, 32, 64), (3, 192, 64, 64),
])
def test_wkv6(bh, s, dh, chunk):
    ks = jax.random.split(RNG, 5)
    r, k, v = (jax.random.normal(ks[i], (bh, s, dh)) for i in range(3))
    lw = -jnp.exp(jnp.clip(jax.random.normal(ks[3], (bh, s, dh)), -8, 0))
    u = jax.random.normal(ks[4], (bh, dh))
    y = wkv6_fwd(r, k, v, lw, u, chunk=chunk, interpret=True)
    ref = wkv6_ref(r, k, v, lw, u)
    np.testing.assert_allclose(y, ref, rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("b,s,d,n,bd,chunk", [
    (2, 128, 128, 8, 128, 32), (1, 64, 256, 16, 128, 64),
    (2, 96, 64, 4, 64, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mamba_scan(b, s, d, n, bd, chunk, dtype):
    ks = jax.random.split(RNG, 6)
    x = jax.random.normal(ks[0], (b, s, d), dtype)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (b, s, d)) - 2).astype(
        dtype)
    a = -jnp.exp(jax.random.normal(ks[2], (d, n)) * 0.3)
    bm = jax.random.normal(ks[3], (b, s, n), dtype)
    cm = jax.random.normal(ks[4], (b, s, n), dtype)
    dd = jax.random.normal(ks[5], (d,))
    y = mamba_scan_fwd(x, delta, a, bm, cm, dd, block_d=bd, chunk=chunk,
                       interpret=True)
    ref = mamba_scan_ref(x, delta, a, bm, cm, dd)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(y.astype(jnp.float32),
                               ref.astype(jnp.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("b,d,h,bb,bhid", [
    (64, 96, 128, 64, 64), (128, 128, 128, 128, 128), (32, 64, 256, 32, 128),
])
def test_lstm_cell(b, d, h, bb, bhid):
    ks = jax.random.split(RNG, 4)
    xh = jax.random.normal(ks[0], (b, d + h))
    w = jax.random.normal(ks[1], (4, d + h, h)) * 0.1
    bias = jax.random.normal(ks[2], (4, h)) * 0.1
    c = jax.random.normal(ks[3], (b, h))
    h1, c1 = lstm_cell_fwd(xh, w, bias, c, block_b=bb, block_h=bhid,
                           interpret=True)
    h2, c2 = lstm_cell_ref(xh, w, bias, c)
    np.testing.assert_allclose(h1, h2, rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(c1, c2, rtol=3e-5, atol=3e-5)


def test_flash_attention_vjp_matches_ref():
    from repro.kernels.flash_attention.ops import flash_attention

    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64))
    k = jax.random.normal(ks[1], (1, 128, 2, 64))
    v = jax.random.normal(ks[2], (1, 128, 2, 64))

    def f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 128, 128,
                                       True) ** 2)

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    for t in g:
        assert bool(jnp.all(jnp.isfinite(t)))


def test_wkv6_matches_model_chunked_path():
    """Kernel agrees with the model's own chunked formulation."""
    from repro.models.rwkv import wkv6_chunked

    ks = jax.random.split(RNG, 5)
    b, s, h, dh = 2, 128, 2, 32
    r, k, v = (jax.random.normal(ks[i], (b, s, h, dh)) for i in range(3))
    lw = -jnp.exp(jnp.clip(jax.random.normal(ks[3], (b, s, h, dh)), -8, 0))
    u = jax.random.normal(ks[4], (h, dh))
    y_model, _ = wkv6_chunked(r, k, v, lw, u,
                              jnp.zeros((b, h, dh, dh)), chunk=32)
    from repro.kernels.rwkv6_wkv.ops import wkv6 as wkv6_op
    y_kernel = wkv6_op(r, k, v, lw, u, 32, True)
    np.testing.assert_allclose(y_kernel, y_model, rtol=5e-4, atol=5e-4)
