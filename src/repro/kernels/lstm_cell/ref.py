"""Oracle for the fused LSTM cell."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def lstm_cell_ref(xh, w, b, c):
    """xh: (B, D+H); w: (4, D+H, H) gate-major (i, f, g, o); b: (4, H);
    c: (B, H)."""
    z = jnp.einsum("bd,gdh->gbh", xh.astype(jnp.float32),
                   w.astype(jnp.float32)) + b.astype(jnp.float32)[:, None]
    i, f, g, o = z[0], z[1], z[2], z[3]
    c_new = jax.nn.sigmoid(f + 1.0) * c.astype(jnp.float32) \
        + jax.nn.sigmoid(i) * jnp.tanh(g)
    h_new = jax.nn.sigmoid(o) * jnp.tanh(c_new)
    return h_new.astype(xh.dtype), c_new.astype(xh.dtype)
