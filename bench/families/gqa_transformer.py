"""Decoder-only transformer with grouped-query attention, as the program
builds it: RMSNorm before each block, rotary positions (rotate-half), causal
softmax attention, a gated SiLU feed-forward and an untied output head, no
biases. The configuration file states where this departs from the
published model.

The reference below is plain ``jax.numpy``, one layer after another, with
nothing of the program imported. It reads the benchmark's weights in the
program's parameter layout: ``embed``, ``layers`` (a one-block period
stacked over depth), ``final_norm``, ``lm_head``.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp

# configuration-file key -> field of the program's ModelConfig
PROGRAM_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "num_hidden_layers": "num_layers",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "norm_epsilon": "norm_eps",
}


def _dims(cfg: dict):
    d, hq, hkv = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    return d, hq, hkv, d // hq


def matmul_params(cfg: dict) -> int:
    """Weights that multiply each token once in the forward pass."""
    d, hq, hkv, dh = _dims(cfg)
    f = cfg["intermediate_size"]
    per_layer = d * hq * dh + 2 * d * hkv * dh + hq * dh * d + d * 2 * f + f * d
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def train_flops(cfg: dict, lens: List[int]) -> float:
    """Model FLOPs of one training step over documents of lengths ``lens``:
    forward plus backward (three forwards), causal attention over each
    document's own length, nothing recomputed, padding not counted."""
    _, hq, _, dh = _dims(cfg)
    pm = matmul_params(cfg)
    total = 0
    for n in lens:
        n = int(n)
        attn = cfg["num_hidden_layers"] * 2 * hq * dh * n * (n + 1)
        total += 2 * pm * n + attn
    return 3.0 * total


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """x: (B, S, H, dh); rotate-half pairs (i, i + dh/2)."""
    s, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


def _layer(x, p: Dict[str, jax.Array], cfg: dict):
    d, hq, hkv, dh = _dims(cfg)
    eps = cfg["norm_epsilon"]
    b, s, _ = x.shape
    a = p["mixer"]
    h = _rms(x, p["mixer_norm"], eps)
    q = (h @ a["wq"]).reshape(b, s, hq, dh)
    k = (h @ a["wk"]).reshape(b, s, hkv, dh)
    v = (h @ a["wv"]).reshape(b, s, hkv, dh)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    group = hq // hkv
    k = jnp.repeat(k, group, axis=2)                 # query head j reads j//group
    v = jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(dh))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, hq * dh)
    x = x + o @ a["wo"]
    f = p["ffn"]
    h = _rms(x, p["ffn_norm"], eps)
    gu = h @ f["wi"]
    g, u = gu[..., :cfg["intermediate_size"]], gu[..., cfg["intermediate_size"]:]
    return x + (jax.nn.silu(g) * u) @ f["wo"]


def loss(params, tokens, labels, cfg: dict):
    """Mean next-token cross-entropy over labels >= 0."""
    x = params["embed"][tokens]
    layers = params["layers"][0]
    for j in range(cfg["num_hidden_layers"]):
        x = _layer(x, jax.tree.map(lambda a: a[j], layers), cfg)
    x = _rms(x, params["final_norm"], cfg["norm_epsilon"])
    logits = x @ params["lm_head"]
    logits = logits[..., :cfg["vocab_size"]]
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
