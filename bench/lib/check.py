"""What decides ``correct``: the timed step against a plain reference.

The program's first three training steps (the window's own call, feed and
compiled step, at the timed sizes) are compared with the reference put
through the same three batches from the same weights:

* ``loss_gap``: the worst step's |program loss - reference loss| over the
  reference loss;
* ``grad_gap``: the first step's gradient as the optimizer received it,
  read back from the first moment after one step (m1 / (1 - beta1)), by
  the worst leaf: |norm(program) - norm(reference)| over the larger of the
  reference leaf's norm and the median leaf's;
* ``change_gap``: the parameters' change after three steps, as the fourth
  step receives them, by the worst leaf, measured the same way. Leaves
  whose reference gradient is under a thousandth of the median leaf's move
  by round-off alone and are left out.

A stacked-layer leaf counts once per layer. The reference is the family's
plain forward at ``highest`` matmul precision, differentiated by JAX, with
its own clipping, schedule and AdamW written out below from the numbers
the configuration file states. Where the configuration states
``reference_row_blocks``, the reference takes each step's gradient over
that many slices of the rows, so that it fits one chip beside its state.
"""
from __future__ import annotations

import importlib
import math
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.weights import leaf_norms, leaf_path

NUMBERS = ("loss_gap", "grad_gap", "change_gap")
FLOOR_SHARE = 1e-3       # leaves under this share of the median gradient


def family(cfgspec: dict):
    return importlib.import_module(f"bench.families.{cfgspec['family']}")


def lr_at(step: int, opt: dict) -> float:
    """Warm-up then cosine to a tenth, by the optimizer step count."""
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    span = max(opt["total_steps"] - opt["warmup_steps"], 1)
    frac = min(max((step - opt["warmup_steps"]) / span, 0.0), 1.0)
    return opt["lr"] * warm * (0.1 + 0.9 * 0.5 * (1.0 + np.cos(np.pi * frac)))


def _decays(path, no_decay: List[str]) -> bool:
    return leaf_path(path)[-1] not in no_decay


def _padded(x: np.ndarray, width: Optional[int], fill: int) -> np.ndarray:
    if width is None or x.shape[1] >= width:
        return x
    return np.pad(x, ((0, 0), (0, width - x.shape[1])), constant_values=fill)


def row_blocks(cfgspec: dict, batch: int) -> int:
    """The configuration's ``reference_row_blocks`` (default 1): how many
    slices of each batch's rows the reference takes its gradient over, one
    after another, so that it fits beside its own state. Raises, naming the
    configuration, unless it is an integer >= 1 that divides ``batch``."""
    n = cfgspec.get("reference_row_blocks", 1)
    if isinstance(n, bool) or not isinstance(n, int) or n < 1 or batch % n:
        raise ValueError(f"{cfgspec['name']}: reference_row_blocks={n!r} "
                         f"must be an integer >= 1 that divides the batch "
                         f"of {batch}")
    return n


def reference_step(cfgspec: dict, blocks: int = 1) -> Callable:
    """The reference's jitted optimizer step, ``(params, m, v, tokens,
    labels, t, lr) -> (params, m, v, loss, clipped gradient norms)``, with
    params, m and v donated.

    With ``blocks`` > 1 the loss and gradient are taken over that many
    slices of the rows in turn, each slice's weighted by its share of the
    batch's labelled positions, so that their sum is the whole batch's mean
    loss and its gradient; clipping and the update then run once."""
    fam = family(cfgspec)
    opt = cfgspec["optimizer"]
    b1, b2, eps, wd = opt["beta1"], opt["beta2"], opt["eps"], \
        opt["weight_decay"]

    def loss_fn(p, tokens, labels):
        return fam.loss(p, tokens, labels, cfgspec["model"])

    def loss_and_grad(p, tokens, labels):
        if blocks == 1:
            return jax.value_and_grad(loss_fn)(p, tokens, labels)
        total = jnp.maximum(jnp.sum(labels >= 0), 1).astype(jnp.float32)

        def block(acc, rows):
            tok, lab = rows
            share = jnp.sum(lab >= 0).astype(jnp.float32) / total
            loss, g = jax.value_and_grad(loss_fn)(p, tok, lab)
            return (acc[0] + share * loss,
                    jax.tree.map(lambda a, x: a + share * x, acc[1], g)), None

        zero = (jnp.zeros((), jnp.float32),
                jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p))
        sliced = jax.tree.map(
            lambda x: x.reshape((blocks, -1) + x.shape[1:]), (tokens, labels))
        (loss, g), _ = jax.lax.scan(block, zero, sliced)
        return loss, jax.tree.map(lambda a, x: a.astype(x.dtype), g, p)

    def step(p, m, v, tokens, labels, t, lr):
        with jax.default_matmul_precision("highest"):
            loss, g = loss_and_grad(p, tokens, labels)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                             for x in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
        g = jax.tree.map(lambda x: x * scale, g)
        m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t

        def upd(path, x, a, c):
            delta = (a / bc1) / (jnp.sqrt(c / bc2) + eps)
            if wd and _decays(path, opt["no_weight_decay"]):
                delta = delta + wd * x
            return x - lr * delta

        p = jax.tree_util.tree_map_with_path(upd, p, m, v)
        return p, m, v, loss, leaf_norms(g)

    return jax.jit(step, donate_argnums=(0, 1, 2))


def reference_steps(cfgspec: dict, params: Any, batches: List[Any], *,
                    rows: Optional[slice] = None,
                    pad_to: Optional[int] = None) -> Dict[str, Any]:
    """Train ``params`` (donated) through ``batches`` with the reference.

    Returns the losses, the first step's clipped gradient norms per leaf
    and the final parameters. ``rows`` keeps only some rows of each batch
    (a planted fault); the kept rows are then taken in gcd(blocks, rows
    kept) slices, ``blocks`` being the configuration's
    ``reference_row_blocks``: with half the rows kept, a slice holds no
    more rows than a slice of the whole batch does.
    ``pad_to`` pads every batch further, to one width, so that one
    compiled reference serves every seed: the extra positions come after
    each document and carry no label, so no loss or gradient changes."""
    opt = cfgspec["optimizer"]
    blocks = row_blocks(cfgspec, len(batches[0].tokens))
    kept = len(batches[0].tokens[rows if rows is not None else slice(None)])
    jstep = reference_step(cfgspec, math.gcd(blocks, kept))
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, g1 = [], None
    for i, b in enumerate(batches):
        tok, lab = _padded(b.tokens, pad_to, 0), _padded(b.labels, pad_to, -1)
        if rows is not None:
            tok, lab = tok[rows], lab[rows]
        params, m, v, loss, gn = jstep(params, m, v, jnp.asarray(tok),
                                       jnp.asarray(lab), float(i + 1),
                                       lr_at(i, opt))
        losses.append(float(loss))
        if g1 is None:
            g1 = {k: float(x) for k, x in gn.items()}
    del m, v
    return {"losses": losses, "grad_norms": g1, "params": params}


def change_norms_fn(init_fn: Callable) -> Callable:
    """``(params, key) -> per-leaf norm of params - init_fn(key)``, jitted,
    so the first weights are made again on the device and never kept."""
    def fn(params, key):
        p0 = init_fn(key)
        return leaf_norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            params, p0))
    return jax.jit(fn)


def reference_readings(cfgspec: dict, init_fn: Callable, key, batches,
                       rows: Optional[slice] = None,
                       pad_to: Optional[int] = None) -> Dict[str, Any]:
    """The reference's readings over ``batches`` from the weights
    ``init_fn(key)``: losses, first clipped gradient and change norms."""
    out = reference_steps(cfgspec, jax.jit(init_fn)(key), batches,
                          rows=rows, pad_to=pad_to)
    out["change_norms"] = {k: float(v) for k, v in
                           change_norms_fn(init_fn)(out.pop("params"),
                                                    key).items()}
    return out


def _worst_leaf(prog: Dict[str, float], ref: Dict[str, float],
                keep: Optional[List[str]] = None) -> float:
    names = keep if keep is not None else list(ref)
    med = float(np.median([ref[k] for k in names]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
               for k in names)


def gaps(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The three numbers compared, from the program's and the reference's
    readings (``losses``, ``grad_norms``, ``change_norms``)."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref["losses"]))
    g = ref["grad_norms"]
    med = float(np.median(list(g.values())))
    moving = [k for k, x in g.items() if x >= FLOOR_SHARE * med]
    return {"loss_gap": float(loss_gap),
            "grad_gap": _worst_leaf(prog["grad_norms"], g),
            "change_gap": _worst_leaf(prog["change_norms"],
                                      ref["change_norms"], moving)}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Correct when every number is finite and within its limit."""
    return all(np.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in NUMBERS)
