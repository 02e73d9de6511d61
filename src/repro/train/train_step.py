"""Train/serve step builders: the functions jit/lowered by launch + trainer.

``build_train_step`` returns a pure ``(train_state, batch) -> (train_state,
metrics)`` with optional microbatch gradient accumulation (scan over
microbatches — compute/comm overlap is left to XLA's latency-hiding
scheduler; each microbatch's gradient all-reduce can overlap the next
microbatch's backward).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import RunConfig, StepKind
from repro.dist.compression import (
    compress_grads,
    decompress_grads,
    init_residual,
)
from repro.dist.sharding import param_specs
from repro.models.model_zoo import Model
from repro.train.optimizer import (
    OptState,
    adamw_update,
    init_opt_state,
    lr_schedule,
)

Params = Any


class TrainState(NamedTuple):
    params: Params
    opt: OptState
    # error-feedback residual for compressed DP gradients; None when the
    # compression method carries no state (tree structure is step-invariant,
    # and None leaves vanish in path-flattened checkpoints)
    ef: Any = None


def init_train_state(model: Model, run: RunConfig, rng: jax.Array
                     ) -> TrainState:
    params = model.init(rng)
    return TrainState(params=params,
                      opt=init_opt_state(params, run.optimizer),
                      ef=init_residual(params,
                                       run.optimizer.grad_compression))


def train_state_specs(state: TrainState, run: RunConfig) -> TrainState:
    """``PartitionSpec`` tree for a train state (shapes or arrays) under
    the production sharding rules of ``run``: params sharded as ZeRO-3
    asks, optimizer moments (and the error-feedback residual, which is
    gradient-shaped per-replica state) sharded whenever FSDP is on."""
    cfg, mesh = run.model, run.mesh
    pspecs = param_specs(state.params, cfg, mesh,
                         run.fsdp and run.zero_stage >= 3,
                         run.fsdp_over_pods, run.moe_full_ep,
                         run.parallelism)
    ospecs = param_specs(state.params, cfg, mesh, run.fsdp,
                         run.fsdp_over_pods, run.moe_full_ep,
                         run.parallelism)
    return TrainState(params=pspecs,
                      opt=OptState(step=P(), m=ospecs, v=ospecs),
                      ef=ospecs if state.ef is not None else None)


def build_train_step(model: Model, run: RunConfig, total_steps: int = 10_000
                     ) -> Callable[[TrainState, Dict[str, jax.Array]],
                                   Tuple[TrainState, Dict[str, jax.Array]]]:
    lr_fn = lr_schedule(run.optimizer, total_steps)
    nmicro = max(run.microbatches, 1)
    # dry-run roofline mode unrolls the accumulation scan so cost_analysis
    # counts every microbatch (DESIGN.md §6)
    scan_unroll = nmicro if run.unroll_layers else 1

    def loss_fn(params, batch):
        loss, metrics = model.loss(params, batch)
        return loss, metrics

    def train_step(state: TrainState, batch: Dict[str, jax.Array]):
        if nmicro == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params, batch)
        else:
            micro = jax.tree.map(
                lambda x: x.reshape((nmicro, x.shape[0] // nmicro)
                                    + x.shape[1:]), batch)

            def acc(carry, mb):
                (loss_a, grads_a) = carry
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(state.params, mb)
                grads = jax.tree.map(jnp.add, grads_a, grads)
                return (loss_a + loss, grads), metrics

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (loss, grads), metrics = jax.lax.scan(
                acc, (jnp.zeros((), jnp.float32), zeros), micro,
                unroll=scan_unroll)
            loss = loss / nmicro
            grads = jax.tree.map(lambda g: g / nmicro, grads)
            metrics = jax.tree.map(lambda m: m[-1], metrics)

        # compressed DP all-reduce: quantize (grads + residual) to the wire
        # format, apply the decompressed gradient, carry the new residual.
        # The compress/decompress pair brackets the cross-replica reduction
        # under SPMD; numerically it is replica-identical, so it also runs
        # (and is tested) on a single device.
        method = run.optimizer.grad_compression
        ef_new = state.ef
        if method != "none":
            if state.ef is not None:
                grads = jax.tree.map(jnp.add, grads, state.ef)
            wire, err = compress_grads(grads, method)
            grads = decompress_grads(wire, method, grads)
            if state.ef is not None:
                ef_new = err

        lr = lr_fn(state.opt.step)
        new_params, new_opt, opt_metrics = adamw_update(
            grads, state.opt, state.params, run.optimizer, lr)
        metrics = dict(metrics, loss=loss, **opt_metrics)
        return TrainState(new_params, new_opt, ef_new), metrics

    return train_step


def build_serve_step(model: Model, run: RunConfig, kind: StepKind):
    """prefill: batch -> (logits, caches). decode: one-token step."""
    if kind == StepKind.PREFILL:
        def prefill(params, batch):
            return model.prefill(params, batch)
        return prefill

    def decode(params, caches, token, cache_index):
        return model.decode_step(params, caches, token, cache_index)
    return decode
