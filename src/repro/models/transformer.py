"""Unified decoder-only LM over a configurable block pattern.

The layer stack is a ``lax.scan`` over *super-layers* (one interleave period
of the block pattern, e.g. jamba's 8-layer mamba/attention period), giving
O(1) trace/compile cost in depth. ``Runtime.unroll_layers`` unrolls the scan
for dry-run cost analysis (DESIGN.md §6); ``Runtime.remat`` checkpoints each
super-layer for training memory.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from repro.configs.base import BlockKind as BK
from repro.configs.base import ModelConfig, RunConfig, ShapeConfig, StepKind
from repro.dist.axes import constrain
from repro.models import attention as attn
from repro.models import mamba as mb
from repro.models import moe as moe_mod
from repro.models import rwkv as rw
from repro.models.layers import (
    Params,
    dense_init,
    embed_init,
    pad_heads,
    padded_vocab,
    rms_norm,
    softmax_xent,
)


@dataclasses.dataclass(frozen=True)
class Runtime:
    """Execution knobs resolved from RunConfig + mesh (model code only sees
    this, never the mesh itself)."""

    tp_degree: int = 1
    attn_chunk: int = 0          # 0 = auto
    unroll_layers: bool = False
    attn_unroll: int = 1
    remat: str = "none"
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    moe_full_ep: bool = False

    @staticmethod
    def from_run(run: RunConfig) -> "Runtime":
        tp = run.mesh.model_degree if run.parallelism == "tp" else 1
        return Runtime(
            tp_degree=tp,
            attn_chunk=run.attn_chunk,
            unroll_layers=bool(run.unroll_layers),
            attn_unroll=max(run.unroll_layers, 1),
            remat=run.remat,
            param_dtype=jnp.dtype(run.param_dtype),
            compute_dtype=jnp.dtype(run.compute_dtype),
            moe_full_ep=run.moe_full_ep,
        )


AUTO_CHUNK_THRESHOLD = 8192
AUTO_CHUNK = 2048
MTP_LOSS_WEIGHT = 0.3
VLM_NUM_PATCHES = 2880           # anyres: 5 tiles x 576 patch tokens


def _auto_chunk(rt: Runtime, seq: int) -> int:
    if rt.attn_chunk:
        return rt.attn_chunk
    if seq >= AUTO_CHUNK_THRESHOLD:
        return AUTO_CHUNK
    return 0


# ---------------------------------------------------------------------------
# blocks


def init_ffn(rng: jax.Array, cfg: ModelConfig, dtype) -> Params:
    k1, k2 = jax.random.split(rng)
    return {"wi": dense_init(k1, (cfg.d_model, 2 * cfg.d_ff), dtype),
            "wo": dense_init(k2, (cfg.d_ff, cfg.d_model), dtype)}


def ffn_forward(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    h = jnp.einsum("bsd,df->bsf", x, p["wi"])
    g, u = jnp.split(h, 2, axis=-1)
    from repro.models.layers import act_fn
    return jnp.einsum("bsf,fd->bsd", act_fn(cfg.act)(g) * u, p["wo"])


def init_block(rng: jax.Array, cfg: ModelConfig, kinds: Tuple[BK, BK],
               rt: Runtime) -> Params:
    mixer_kind, ffn_kind = kinds
    dt = rt.param_dtype
    k1, k2 = jax.random.split(rng)
    p: Params = {"mixer_norm": jnp.ones((cfg.d_model,), dt),
                 "ffn_norm": jnp.ones((cfg.d_model,), dt)}
    if mixer_kind == BK.ATTENTION:
        hq = pad_heads(cfg.num_heads, rt.tp_degree)
        p["mixer"] = attn.init_gqa(k1, cfg, dt, hq)
    elif mixer_kind == BK.MLA:
        p["mixer"] = attn.init_mla(k1, cfg, dt)
    elif mixer_kind == BK.MAMBA:
        p["mixer"] = mb.init_mamba(k1, cfg, dt)
    elif mixer_kind == BK.RWKV:
        p["mixer"] = rw.init_time_mix(k1, cfg, dt, rt.tp_degree)
    else:
        raise ValueError(mixer_kind)
    if ffn_kind == BK.DENSE_FFN:
        p["ffn"] = init_ffn(k2, cfg, dt)
    elif ffn_kind == BK.MOE_FFN:
        p["ffn"] = moe_mod.init_moe(k2, cfg, dt)
    elif ffn_kind == BK.RWKV_CHANNEL:
        p["ffn"] = rw.init_channel_mix(k2, cfg, dt)
    else:
        raise ValueError(ffn_kind)
    return p


def init_block_cache(cfg: ModelConfig, kinds: Tuple[BK, BK], batch: int,
                     max_len: int, rt: Runtime) -> Dict[str, Any]:
    mixer_kind, ffn_kind = kinds
    dt = rt.compute_dtype
    dh = cfg.resolved_head_dim
    cache: Dict[str, Any] = {}
    if mixer_kind == BK.ATTENTION:
        cache["mixer"] = (jnp.zeros((batch, max_len, cfg.num_kv_heads, dh), dt),
                          jnp.zeros((batch, max_len, cfg.num_kv_heads, dh), dt))
    elif mixer_kind == BK.MLA:
        m = cfg.mla
        cache["mixer"] = (jnp.zeros((batch, max_len, m.kv_lora_rank), dt),
                          jnp.zeros((batch, max_len, m.qk_rope_head_dim), dt))
    elif mixer_kind == BK.MAMBA:
        cache["mixer"] = mb.init_mamba_cache(cfg, batch, dt)
    elif mixer_kind == BK.RWKV:
        cache["mixer"] = rw.init_time_mix_cache(cfg, batch, dt, rt.tp_degree)
    if ffn_kind == BK.RWKV_CHANNEL:
        cache["ffn"] = rw.init_channel_mix_cache(cfg, batch, dt)
    else:
        cache["ffn"] = {}
    return cache


def block_forward(p: Params, x: jax.Array, cfg: ModelConfig,
                  kinds: Tuple[BK, BK], rt: Runtime, *,
                  positions: jax.Array,
                  cache: Optional[Dict[str, Any]] = None,
                  cache_index: Optional[jax.Array] = None,
                  return_cache: bool = False, causal: bool = True):
    mixer_kind, ffn_kind = kinds
    aux = jnp.zeros((), jnp.float32)
    new_cache: Dict[str, Any] = {}
    chunk = _auto_chunk(rt, x.shape[1])
    def _name(t: jax.Array) -> jax.Array:
        # post-TP-collective intermediates; the save_boundaries remat
        # policy keeps them so recompute skips re-executing the
        # all-reduces (EXPERIMENTS.md §Perf)
        if rt.remat == "save_boundaries":
            return jax.ad_checkpoint.checkpoint_name(t, "block_boundary")
        return t

    # the named scopes only name the HLO ops (op_name metadata), so that a
    # profile splits device time into mixer and FFN, forward and backward
    with jax.named_scope("mixer"):
        h = rms_norm(x, p["mixer_norm"], cfg.norm_eps)
        mc = cache.get("mixer") if cache is not None else None
        if mixer_kind == BK.ATTENTION:
            y, c = attn.gqa_forward(p["mixer"], h, cfg, positions=positions,
                                    causal=causal, chunk=chunk,
                                    unroll=rt.attn_unroll, cache=mc,
                                    cache_index=cache_index,
                                    return_kv=return_cache)
        elif mixer_kind == BK.MLA:
            y, c = attn.mla_forward(p["mixer"], h, cfg, positions=positions,
                                    chunk=chunk, unroll=rt.attn_unroll,
                                    cache=mc, cache_index=cache_index,
                                    return_kv=return_cache)
        elif mixer_kind == BK.MAMBA:
            y, c = mb.mamba_forward(p["mixer"], h, cfg, cache=mc,
                                    return_state=return_cache)
        else:
            y, c = rw.time_mix_forward(p["mixer"], h, cfg, cache=mc,
                                       return_state=return_cache)
        if c is not None:
            new_cache["mixer"] = c
        x = x + _name(y)

    with jax.named_scope("ffn"):
        h = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        fc = cache.get("ffn") if cache is not None else None
        if ffn_kind == BK.DENSE_FFN:
            y = ffn_forward(p["ffn"], h, cfg)
        elif ffn_kind == BK.MOE_FFN:
            y, aux = moe_mod.moe_forward(p["ffn"], h, cfg, rt.tp_degree,
                                         rt.moe_full_ep)
        else:
            y, c2 = rw.channel_mix_forward(p["ffn"], h, cfg,
                                           cache=fc if fc else None,
                                           return_state=return_cache)
            if c2 is not None:
                new_cache["ffn"] = c2
        if "ffn" not in new_cache:
            new_cache["ffn"] = {}
        x = x + _name(y)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# the model


class TransformerLM:
    """Decoder-only LM (all non-enc-dec assigned archs)."""

    def __init__(self, cfg: ModelConfig, rt: Runtime):
        assert cfg.num_layers % cfg.interleave_period == 0, cfg.name
        self.cfg = cfg
        self.rt = rt
        self.n_periods = cfg.num_layers // cfg.interleave_period
        self.vocab_p = padded_vocab(cfg.vocab_size)

    # -- params -----------------------------------------------------------
    def init(self, rng: jax.Array) -> Params:
        cfg, rt = self.cfg, self.rt
        k_emb, k_layers, k_head, k_mtp = jax.random.split(rng, 4)
        layer_keys = jax.random.split(k_layers, self.n_periods)

        def one_period(k):
            ks = jax.random.split(k, cfg.interleave_period)
            return tuple(init_block(ks[i], cfg, kinds, rt)
                         for i, kinds in enumerate(cfg.pattern))

        layers = jax.vmap(one_period)(layer_keys)   # leaves: (n_periods, ...)
        p: Params = {
            "embed": embed_init(k_emb, (self.vocab_p, cfg.d_model),
                                rt.param_dtype),
            "layers": layers,
            "final_norm": jnp.ones((cfg.d_model,), rt.param_dtype),
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = dense_init(k_head, (cfg.d_model, self.vocab_p),
                                      rt.param_dtype)
        if cfg.mtp_depth:
            km1, km2 = jax.random.split(k_mtp)
            p["mtp"] = {
                "proj": dense_init(km1, (2 * cfg.d_model, cfg.d_model),
                                   rt.param_dtype),
                "block": init_block(km2, cfg, cfg.pattern[0], rt),
                "norm_h": jnp.ones((cfg.d_model,), rt.param_dtype),
                "norm_e": jnp.ones((cfg.d_model,), rt.param_dtype),
            }
        return p

    # -- helpers ----------------------------------------------------------
    def _embed(self, p: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        with jax.named_scope("embed"):
            x = p["embed"][batch["tokens"]].astype(self.rt.compute_dtype)
            x = constrain(x, "dp", None, None)
            if self.cfg.frontend == "image_patches" and "patches" in batch:
                x = jnp.concatenate(
                    [batch["patches"].astype(self.rt.compute_dtype), x],
                    axis=1)
        return x

    def _head(self, p: Params, x: jax.Array) -> jax.Array:
        x = rms_norm(x, p["final_norm"], self.cfg.norm_eps)
        w = p["embed"].T if self.cfg.tie_embeddings else p["lm_head"]
        return constrain(jnp.einsum("bsd,dv->bsv", x, w), "dp", None, "tp")

    def _stack(self, p: Params, x: jax.Array, positions: jax.Array, *,
               caches=None, cache_index=None, return_caches=False):
        cfg, rt = self.cfg, self.rt

        def super_layer(carry, xs):
            x, aux = carry
            layer_p, layer_cache = xs
            new_caches = []
            for j, kinds in enumerate(cfg.pattern):
                x, nc, a = block_forward(
                    layer_p[j], x, cfg, kinds, rt, positions=positions,
                    cache=None if layer_cache is None else layer_cache[j],
                    cache_index=cache_index, return_cache=return_caches)
                new_caches.append(nc)
                aux = aux + a
            return (x, aux), tuple(new_caches)

        fn = super_layer
        if rt.remat == "block":
            fn = jax.checkpoint(super_layer)
        elif rt.remat == "save_boundaries":
            fn = jax.checkpoint(
                super_layer,
                policy=jax.checkpoint_policies.save_only_these_names(
                    "block_boundary"))
        if caches is None:
            # scan xs must be arrays; thread a dummy index for the cache slot
            def fn_nocache(carry, xs_):
                layer_p, _ = xs_
                return fn(carry, (layer_p, None))

            (x, aux), caches_out = jax.lax.scan(
                fn_nocache, (x, jnp.zeros((), jnp.float32)),
                (p["layers"], jnp.arange(self.n_periods)),
                unroll=self.n_periods if rt.unroll_layers else 1)
        else:
            (x, aux), caches_out = jax.lax.scan(
                fn, (x, jnp.zeros((), jnp.float32)), (p["layers"], caches),
                unroll=self.n_periods if rt.unroll_layers else 1)
        return x, aux, caches_out

    # -- public entry points ----------------------------------------------
    def loss(self, p: Params, batch: Dict[str, jax.Array]
             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        cfg = self.cfg
        x = self._embed(p, batch)
        positions = jnp.arange(x.shape[1])
        x, aux, _ = self._stack(p, x, positions)
        labels = batch["labels"]
        if cfg.frontend == "image_patches" and "patches" in batch:
            # image positions carry no LM loss
            pad = jnp.full(batch["patches"].shape[:2], -1, labels.dtype)
            labels = jnp.concatenate([pad, labels], axis=1)
        with jax.named_scope("head_loss"):
            logits = self._head(p, x)
            loss = softmax_xent(logits, labels, cfg.vocab_size)
        metrics = {"xent": loss, "aux": aux}
        if cfg.mtp_depth and "mtp" in p:
            loss_mtp = self._mtp_loss(p, x, batch, positions)
            metrics["mtp"] = loss_mtp
            loss = loss + MTP_LOSS_WEIGHT * loss_mtp
        return loss + aux, metrics

    def _mtp_loss(self, p: Params, h: jax.Array, batch, positions):
        """DeepSeek-V3-style multi-token prediction: one extra block predicts
        token t+2 from [h_t ; emb(token_{t+1})]."""
        cfg, rt = self.cfg, self.rt
        mtp = p["mtp"]
        tokens, labels = batch["tokens"], batch["labels"]
        emb_next = p["embed"][jnp.roll(tokens, -1, axis=1)].astype(h.dtype)
        feat = jnp.concatenate([
            rms_norm(h, mtp["norm_h"], cfg.norm_eps),
            rms_norm(emb_next, mtp["norm_e"], cfg.norm_eps)], axis=-1)
        if cfg.frontend == "image_patches" and "patches" in batch:
            feat = feat[:, batch["patches"].shape[1]:]
        x = jnp.einsum("bsd,de->bse", feat, mtp["proj"])

        def mtp_block(bp, xx):
            return block_forward(bp, xx, cfg, cfg.pattern[0], rt,
                                 positions=jnp.arange(xx.shape[1]))[0]

        if rt.remat == "block":
            mtp_block = jax.checkpoint(mtp_block)
        x = mtp_block(mtp["block"], x)
        logits = self._head(p, x)
        labels2 = jnp.concatenate(
            [labels[:, 1:], jnp.full_like(labels[:, :1], -1)], axis=1)
        return softmax_xent(logits, labels2, cfg.vocab_size)

    def prefill(self, p: Params, batch: Dict[str, jax.Array],
                pos0: jax.Array | int = 0):
        """Prefill a prompt. ``pos0`` offsets the rope positions so a prompt
        can be placed at an absolute cache offset (continuous-batching slot
        admission); the causal mask is local to the window either way."""
        x = self._embed(p, batch)
        positions = jnp.asarray(pos0, jnp.int32) + jnp.arange(x.shape[1])
        x, _, caches = self._stack(p, x, positions, return_caches=True)
        logits = self._head(p, x[:, -1:])
        return logits, caches

    def init_cache(self, batch: int, max_len: int):
        cfg, rt = self.cfg, self.rt

        def one(_):
            return tuple(init_block_cache(cfg, kinds, batch, max_len, rt)
                         for kinds in cfg.pattern)

        # stacked over periods to match the scan layout
        return jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[one(i) for i in range(self.n_periods)])

    def decode_step(self, p: Params, caches, token: jax.Array,
                    cache_index: jax.Array):
        """token: (B, 1) int32; cache_index: scalar int32 (current length)."""
        x = p["embed"][token].astype(self.rt.compute_dtype)
        positions = cache_index[None] if cache_index.ndim == 0 \
            else cache_index
        x, _, new_caches = self._stack(p, x, positions, caches=caches,
                                       cache_index=cache_index)
        logits = self._head(p, x)
        return logits[:, 0], new_caches

    # -- specs --------------------------------------------------------------
    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        if shape.step == StepKind.TRAIN or shape.step == StepKind.PREFILL:
            if cfg.frontend == "image_patches":
                n_img = min(VLM_NUM_PATCHES, s // 2)
                specs = {
                    "tokens": jax.ShapeDtypeStruct((b, s - n_img), jnp.int32),
                    "patches": jax.ShapeDtypeStruct((b, n_img, cfg.d_model),
                                                    self.rt.compute_dtype),
                }
                if shape.step == StepKind.TRAIN:
                    specs["labels"] = jax.ShapeDtypeStruct((b, s - n_img),
                                                           jnp.int32)
                return specs
            specs = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
            if shape.step == StepKind.TRAIN:
                specs["labels"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
            return specs
        # decode: one token against a seq_len cache
        return {"token": jax.ShapeDtypeStruct((b, 1), jnp.int32),
                "cache_index": jax.ShapeDtypeStruct((), jnp.int32)}
