"""Mixture-of-experts: top-k router + capacity-bounded expert-parallel
dispatch under ``shard_map``.

TPU-native formulation (DESIGN.md §5): activations are already replicated
across the model axis between blocks, so each model shard *selects* the
assignments routed to its local experts from its resident tokens, scatters
them into a fixed-capacity buffer (E_local, C, d), runs the expert GLU as a
batched einsum, gathers back, and the partial outputs (plus f-sharded shared
experts) merge in ONE psum over the model axis — the same collective a
Megatron FFN already pays. No all-to-all, no replicated expert compute.

Token ranks within an expert use a sort-based positioning (O(T log T) and
O(T) memory instead of the (T, E) one-hot cumsum). Scatter/gather loop over
the k routing slots so per-slot temporaries are (T, d), not (T*k, d).

Capacity is per (expert, data shard) — GShard local-capacity semantics —
keeping iteration cost a pure function of sequence length, the property
SeqPoint relies on (DESIGN.md §7).

Without a mesh (unit tests / smoke), a mathematically identical single-device
path runs.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.dist.axes import _resolve as _resolve_axis
from repro.dist.axes import current_mesh_axes
from repro.models.layers import act_fn, dense_init

Params = Dict[str, Any]


def expert_capacity(num_tokens: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    cap = num_tokens * m.experts_per_token / m.num_experts * m.capacity_factor
    return max(8, int(math.ceil(cap / 8.0)) * 8)


def init_moe(rng: jax.Array, cfg: ModelConfig, dtype) -> Params:
    m = cfg.moe
    d = cfg.d_model
    f = m.expert_d_ff or cfg.d_ff
    ks = jax.random.split(rng, 8)
    p: Params = {
        "router": dense_init(ks[0], (d, m.num_experts), jnp.float32),
        "e_wg": dense_init(ks[1], (m.num_experts, d, f), dtype),
        "e_wu": dense_init(ks[2], (m.num_experts, d, f), dtype),
        "e_wo": dense_init(ks[3], (m.num_experts, f, d), dtype),
    }
    if m.num_shared_experts:
        fs = f * m.num_shared_experts
        p["s_wg"] = dense_init(ks[4], (d, fs), dtype)
        p["s_wu"] = dense_init(ks[5], (d, fs), dtype)
        p["s_wo"] = dense_init(ks[6], (fs, d), dtype)
    return p


# ---------------------------------------------------------------------------
# routing helpers (shared by both paths; everything is per-shard local)


def _route(xt: jax.Array, router: jax.Array, k: int):
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, k)
    gate = gate / jnp.maximum(jnp.sum(gate, axis=-1, keepdims=True), 1e-9)
    return probs, gate, eidx


def _positions(flat_e: jax.Array, num_experts: int) -> jax.Array:
    """Rank of each assignment within its expert (sort-based, stable)."""
    tk = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    run_start = jnp.searchsorted(sorted_e, jnp.arange(num_experts))
    pos_sorted = jnp.arange(tk) - run_start[sorted_e]
    return jnp.zeros((tk,), jnp.int32).at[order].set(
        pos_sorted.astype(jnp.int32))


def _expert_glu(buf: jax.Array, e_wg, e_wu, e_wo, act: str) -> jax.Array:
    g = jnp.einsum("ecd,edf->ecf", buf, e_wg)
    u = jnp.einsum("ecd,edf->ecf", buf, e_wu)
    return jnp.einsum("ecf,efd->ecd", act_fn(act)(g) * u, e_wo)


def _moe_math(xt, router, e_wg, e_wu, e_wo, cfg: ModelConfig, *,
              first_expert=None, num_experts_global: int = 0):
    """Single-shard MoE math over the (local) expert slice [first_expert,
    first_expert + E_loc). ``first_expert=None`` means all experts are
    local."""
    m = cfg.moe
    t, d = xt.shape
    e_loc = e_wg.shape[0]
    e_glob = num_experts_global or m.num_experts
    probs, gate, eidx = _route(xt, router, m.experts_per_token)
    cap = expert_capacity(t, cfg)

    flat_e = eidx.reshape(-1)
    pos = _positions(flat_e, e_glob).reshape(t, m.experts_per_token)

    if first_expert is None:
        local_e = eidx
        mine = jnp.ones_like(eidx, dtype=bool)
    else:
        local_e = eidx - first_expert
        mine = (local_e >= 0) & (local_e < e_loc)
    keep = mine & (pos < cap)
    dest_e = jnp.where(keep, local_e, 0)
    dest_c = jnp.where(keep, pos, cap)                     # cap col = spill

    buf = jnp.zeros((e_loc, cap + 1, d), xt.dtype)
    for slot in range(m.experts_per_token):
        buf = buf.at[dest_e[:, slot], dest_c[:, slot]].set(
            xt, mode="drop")
    y_buf = _expert_glu(buf[:, :cap], e_wg, e_wu, e_wo, cfg.act)
    y_buf = jnp.pad(y_buf, ((0, 0), (0, 1), (0, 0)))       # zero spill col

    y = jnp.zeros((t, d), jnp.float32)
    for slot in range(m.experts_per_token):
        contrib = y_buf[dest_e[:, slot], dest_c[:, slot]]
        contrib = jnp.where(keep[:, slot, None], contrib, 0.0)
        y = y + contrib.astype(jnp.float32) * gate[:, slot, None]

    # Switch-style load-balance loss (local estimate; counts via scatter-add
    # instead of a (T, k, E) one-hot)
    me = jnp.mean(probs, axis=0)
    counts = jnp.zeros((e_glob,), jnp.float32).at[flat_e].add(1.0)
    assign = counts / flat_e.shape[0]
    aux = e_glob * jnp.sum(me * assign) * m.router_aux_coef
    return y, aux


def _shared_glu(xt, s_wg, s_wu, s_wo, act: str) -> jax.Array:
    g = jnp.einsum("td,df->tf", xt, s_wg)
    u = jnp.einsum("td,df->tf", xt, s_wu)
    return jnp.einsum("tf,fd->td", act_fn(act)(g) * u, s_wo)


# ---------------------------------------------------------------------------
# entry point


def moe_forward(p: Params, x: jax.Array, cfg: ModelConfig, tp: int = 1,
                full_ep: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, aux_loss). Uses the shard_map EP path under a
    mesh, the plain path otherwise. ``full_ep`` shards experts over
    (data x model) with an all-to-all token exchange — see
    ``_moe_forward_full_ep`` (EXPERIMENTS.md §Perf hillclimb 1)."""
    m = cfg.moe
    b, s, d = x.shape
    axes = current_mesh_axes()
    if "model" in axes and full_ep:
        return _moe_forward_full_ep(p, x, cfg)
    if "model" in axes:
        return _moe_forward_sharded(p, x, cfg)

    xt = x.reshape(b * s, d)
    y, aux = _moe_math(xt, p["router"], p["e_wg"], p["e_wu"], p["e_wo"], cfg)
    if m.num_shared_experts:
        y = y + _shared_glu(xt, p["s_wg"], p["s_wu"], p["s_wo"],
                            cfg.act).astype(jnp.float32)
    return y.astype(x.dtype).reshape(b, s, d), aux


def _moe_forward_sharded(p: Params, x: jax.Array, cfg: ModelConfig):
    from jax._src import mesh as _mesh_lib

    mesh = _mesh_lib.thread_resources.env.physical_mesh
    m = cfg.moe
    b, s, d = x.shape
    axes = tuple(mesh.axis_names)
    dp_axes = tuple(a for a in axes if a in ("pod", "data"))
    dp_degree = 1
    for a in dp_axes:
        dp_degree *= mesh.shape[a]
    tp_degree = mesh.shape["model"]
    batch_split = dp_axes if (dp_axes and b % dp_degree == 0) else ()
    bspec = (batch_split if len(batch_split) != 1 else batch_split[0]) \
        if batch_split else None
    ep = m.num_experts % tp_degree == 0
    shared = bool(m.num_shared_experts)

    x_spec = P(bspec, None, None)
    if ep:
        ew_spec = (P("model", None, None), P("model", None, None),
                   P("model", None, None))
    else:
        ew_spec = (P(None, None, "model"), P(None, None, "model"),
                   P(None, "model", None))
    sw_spec = (P(None, "model"), P(None, "model"), P("model", None))

    def local_fn(x, router, e_wg, e_wu, e_wo, s_wg, s_wu, s_wo):
        bl, sl, _ = x.shape
        xt = x.reshape(bl * sl, d)
        if ep:
            e_loc = m.num_experts // tp_degree
            first = jax.lax.axis_index("model") * e_loc
        else:
            first = None
        y, aux = _moe_math(xt, router, e_wg, e_wu, e_wo, cfg,
                           first_expert=first,
                           num_experts_global=m.num_experts)
        if shared:
            y = y + _shared_glu(xt, s_wg, s_wu, s_wo,
                                cfg.act).astype(jnp.float32)
        y = jax.lax.psum(y.astype(x.dtype), "model")
        if not ep:
            # expert-TP computes every expert's f-shard: psum already merged
            pass
        if batch_split:
            aux = jax.lax.pmean(aux, dp_axes)
        aux = jax.lax.pmean(aux, "model")
        return y.reshape(bl, sl, d), aux

    if shared:
        sw = (p["s_wg"], p["s_wu"], p["s_wo"])
    else:
        sw = (jnp.zeros((1, 1), x.dtype),) * 3
        sw_spec = (P(None, None),) * 3
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(x_spec, P(None, None), *ew_spec, *sw_spec),
        out_specs=(x_spec, P()))
    y, aux = fn(x, p["router"], p["e_wg"], p["e_wu"], p["e_wo"], *sw)
    return y, aux


def _moe_forward_full_ep(p: Params, x: jax.Array, cfg: ModelConfig):
    """Full expert parallelism over (data x model): each device owns
    E / num_devices experts RESIDENT (no ZeRO gathers, no cross-data expert
    gradient reduction), and tokens move to their experts through a
    fixed-capacity all-to-all — DeepSeek-V3's own EP design restated for the
    TPU mesh. Beyond-paper optimization; baseline keeps model-axis EP.

    Per device: send buffer (n_dev, C_pair, d) with C_pair =
    T_loc*k/n_dev*cf; a2a out, batched GLU over (E_loc, n_dev*C_pair, d),
    a2a back, gate-combine at the source. Gradients flow through the a2a
    transposes; expert weight grads stay device-local.
    """
    from jax._src import mesh as _mesh_lib

    mesh = _mesh_lib.thread_resources.env.physical_mesh
    m = cfg.moe
    b, s, d = x.shape
    axes = tuple(mesh.axis_names)
    ep_axes = tuple(a for a in axes if a in ("data", "model"))
    n_dev = 1
    for a in ep_axes:
        n_dev *= mesh.shape[a]
    assert m.num_experts % n_dev == 0, (m.num_experts, n_dev)
    e_loc = m.num_experts // n_dev
    dp_axes = tuple(a for a in axes if a in ("pod", "data"))
    dp_degree = 1
    for a in dp_axes:
        dp_degree *= mesh.shape[a]
    tp_size = mesh.shape["model"]
    assert b % dp_degree == 0
    bspec = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    # train/prefill: tokens split over model on sequence so every EP rank
    # holds a distinct slice. decode (S < tp): tokens replicated over model,
    # assignments partitioned by routing slot across model ranks, outputs
    # psum'd — same a2a pattern, no divisibility constraint.
    seq_split = s % tp_size == 0 and s >= tp_size
    x_spec = P(bspec, "model" if seq_split else None, None)

    def local_fn(x, router, e_wg, e_wu, e_wo):
        bl, sl, _ = x.shape
        t = bl * sl
        xt = x.reshape(t, d)
        probs, gate, eidx = _route(xt, router, m.experts_per_token)
        # capacity per (source device, destination device) pair; no 8-row
        # floor — decode sends O(1) tokens per pair
        raw = t * m.experts_per_token / n_dev * m.capacity_factor
        cap = int(-(-raw // 8)) * 8 if raw > 8 else max(1, int(-(-raw // 1)))
        flat_e = eidx.reshape(-1)
        dest_dev = flat_e // e_loc
        dest_slot = flat_e % e_loc
        pos = _positions(dest_dev, n_dev).reshape(t, m.experts_per_token)
        keep = pos < cap
        if not seq_split:
            rank = jax.lax.axis_index("model")
            mine = (jnp.arange(t * m.experts_per_token) % tp_size) == rank
            keep = keep & mine.reshape(t, m.experts_per_token)
        dd = jnp.where(keep, dest_dev.reshape(t, -1), 0)
        dc = jnp.where(keep, pos, cap)
        send = jnp.zeros((n_dev, cap + 1, d), x.dtype)
        send_e = jnp.zeros((n_dev, cap + 1), jnp.int32)
        for slot in range(m.experts_per_token):
            send = send.at[dd[:, slot], dc[:, slot]].set(xt, mode="drop")
            send_e = send_e.at[dd[:, slot], dc[:, slot]].set(
                dest_slot.reshape(t, -1)[:, slot], mode="drop")
        send, send_e = send[:, :cap], send_e[:, :cap]
        recv = jax.lax.all_to_all(send, ep_axes, split_axis=0,
                                  concat_axis=0, tiled=True)
        recv_e = jax.lax.all_to_all(send_e, ep_axes, split_axis=0,
                                    concat_axis=0, tiled=True)
        rt = recv.reshape(n_dev * cap, d)
        re = recv_e.reshape(n_dev * cap)
        # dispatch received tokens into the local experts' buffers
        cap2 = n_dev * cap          # worst case: all land on one expert
        pos2 = _positions(re, e_loc)
        buf = jnp.zeros((e_loc, cap2, d), x.dtype)
        buf = buf.at[re, pos2].set(rt, mode="drop")
        y_buf = _expert_glu(buf, e_wg, e_wu, e_wo, cfg.act)
        y_tok = y_buf[re, pos2]
        back = y_tok.reshape(n_dev, cap, d)
        back = jax.lax.all_to_all(back, ep_axes, split_axis=0,
                                  concat_axis=0, tiled=True)
        back = jnp.pad(back, ((0, 0), (0, 1), (0, 0)))
        y = jnp.zeros((t, d), jnp.float32)
        for slot in range(m.experts_per_token):
            contrib = back[dd[:, slot], dc[:, slot]]
            contrib = jnp.where(keep[:, slot, None], contrib, 0.0)
            y = y + contrib.astype(jnp.float32) * gate[:, slot, None]
        y = y.astype(x.dtype)
        if not seq_split:
            y = jax.lax.psum(y, "model")     # slots partitioned over ranks
        me = jnp.mean(probs, axis=0)
        counts = jnp.zeros((m.num_experts,), jnp.float32).at[flat_e].add(1.0)
        aux = m.num_experts * jnp.sum(me * counts / flat_e.shape[0]) \
            * m.router_aux_coef
        aux = jax.lax.pmean(jax.lax.pmean(aux, dp_axes), "model")
        return y.reshape(bl, sl, d), aux

    ew_spec = tuple(P(("data", "model"), None, None) for _ in range(3))
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(x_spec, P(None, None), *ew_spec),
        out_specs=(x_spec, P()))
    y, aux = fn(x, p["router"], p["e_wg"], p["e_wu"], p["e_wo"])
    if m.num_shared_experts:
        # shared experts stay TP-sharded in auto-SPMD land (partial-sum
        # psum handled by the partitioner; weights too big to replicate)
        xt = x.reshape(b * s, d)
        y = y + _shared_glu(xt, p["s_wg"], p["s_wu"],
                            p["s_wo"], cfg.act).astype(y.dtype).reshape(
                                b, s, d)
    return y, aux
