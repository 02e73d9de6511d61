"""RWKV-6 (Finch) as the program builds it: RMSNorm before each block, a
time-mix with static token-shift mixing, data-dependent decay through a
low-rank projection (log-decay clamped to [-1, -1e-6]), a per-channel bonus
``u``, per-head normalisation and a SiLU gate, then a channel-mix with a
squared-ReLU key and a sigmoid receptance. The configuration file states
where this departs from the published model.

The reference runs the WKV recurrence one token after another, the plainest
form of it, with nothing of the program imported. It reads the benchmark's
weights in the program's parameter layout: ``embed``, ``layers`` (a
(time-mix, channel-mix) period stacked over depth), ``final_norm``,
``lm_head``.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp

PROGRAM_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "num_layers",
    "vocab_size": "vocab_size",
    "head_size": "rwkv_head_dim",
    "layer_norm_epsilon": "norm_eps",
}

DECAY_LORA = 64          # rank of the decay projection (w_a1, w_a2)
HEAD_NORM_EPS = 1e-5


def matmul_params(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    time_mix = 5 * d * d + 2 * d * DECAY_LORA
    channel_mix = 2 * d * f + d * d
    return cfg["num_hidden_layers"] * (time_mix + channel_mix) \
        + d * cfg["vocab_size"]


def train_flops(cfg: dict, lens: List[int]) -> float:
    """Model FLOPs of one training step: three forwards of the projections
    and head, plus the WKV recurrence at 4 * head_size**2 per head and token
    (state update and read-out); elementwise work is not counted."""
    d, dh = cfg["hidden_size"], cfg["head_size"]
    wkv = cfg["num_hidden_layers"] * 4 * d * dh
    per_token = 2 * matmul_params(cfg) + wkv
    return 3.0 * per_token * float(sum(int(n) for n in lens))


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _prev(x):
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def _wkv(r, k, v, lw, u):
    """Sequential recurrence; r, k, v, lw: (B, S, H, dh) float32."""
    b, s, h, dh = r.shape

    def step(state, xs):
        rt, kt, vt, wt = xs
        y = jnp.einsum("bhi,bhij->bhj", rt, state) + \
            jnp.sum(rt * u * kt, -1, keepdims=True) * vt
        state = jnp.exp(wt)[..., None] * state + kt[..., None] * vt[..., None, :]
        return state, y

    xs = tuple(jnp.swapaxes(t, 0, 1) for t in (r, k, v, lw))
    _, ys = jax.lax.scan(step, jnp.zeros((b, h, dh, dh), jnp.float32), xs)
    return jnp.swapaxes(ys, 0, 1)


def _time_mix(x, p, cfg):
    b, s, d = x.shape
    dh = cfg["head_size"]
    h = d // dh
    xx = _prev(x) - x
    mix = {n: x + xx * p["mu_" + n] for n in "rkvwg"}
    f32 = jnp.float32
    r = (mix["r"] @ p["w_r"]).astype(f32).reshape(b, s, h, dh)
    k = (mix["k"] @ p["w_k"]).astype(f32).reshape(b, s, h, dh)
    v = (mix["v"] @ p["w_v"]).astype(f32).reshape(b, s, h, dh)
    g = jax.nn.silu(mix["g"] @ p["w_g"])
    ww = p["w0"].astype(f32) + jnp.tanh(
        mix["w"].astype(f32) @ p["w_a1"].astype(f32)) @ p["w_a2"].astype(f32)
    lw = jnp.clip(-jnp.exp(ww), -1.0, -1e-6).reshape(b, s, h, dh)
    u = p["u"].astype(f32).reshape(h, dh)
    y = _wkv(r, k, v, lw, u)
    mu = jnp.mean(y, -1, keepdims=True)
    var = jnp.mean(jnp.square(y - mu), -1, keepdims=True)
    y = (y - mu) * jax.lax.rsqrt(var + HEAD_NORM_EPS)
    y = y * p["ln_w"].astype(f32) + p["ln_b"].astype(f32)
    return (y.reshape(b, s, d).astype(x.dtype) * g) @ p["w_o"]


def _channel_mix(x, p):
    xx = _prev(x) - x
    xk, xr = x + xx * p["mu_k"], x + xx * p["mu_r"]
    kk = jnp.square(jax.nn.relu(xk @ p["w_k"]))
    return jax.nn.sigmoid(xr @ p["w_r"]) * (kk @ p["w_v"])


def _layer(x, tm: Dict, cm: Dict, norms: Dict, cfg: dict):
    eps = cfg["layer_norm_epsilon"]
    x = x + _time_mix(_rms(x, norms["mixer_norm"], eps), tm, cfg)
    return x + _channel_mix(_rms(x, norms["ffn_norm"], eps), cm)


def loss(params, tokens, labels, cfg: dict):
    """Mean next-token cross-entropy over labels >= 0."""
    x = params["embed"][tokens]
    block = params["layers"][0]

    def one_layer(x, j):
        pick = lambda t: jax.tree.map(lambda a: a[j], t)
        norms = {"mixer_norm": block["mixer_norm"][j],
                 "ffn_norm": block["ffn_norm"][j]}
        return _layer(x, pick(block["mixer"]), pick(block["ffn"]), norms,
                      cfg)

    for j in range(cfg["num_hidden_layers"]):
        # recompute each layer in the backward pass: the token-by-token
        # state would not fit beside the optimizer state otherwise
        x = jax.checkpoint(one_layer, static_argnums=1)(x, j)
    x = _rms(x, params["final_norm"], cfg["layer_norm_epsilon"])
    logits = x @ params["lm_head"]
    logits = logits[..., :cfg["vocab_size"]]
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
