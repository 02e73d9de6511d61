"""Seeded weights in the program's parameter layout, made on the device.

The layout (the tree of leaf names and shapes) is read from the program
with ``jax.eval_shape``; the values are the benchmark's own, drawn from
``--seed`` by the rules in the configuration file's ``init`` group:

* ``const``: leaf name -> value (norm gains, biases);
* ``std``: leaf name -> standard deviation of a zero-mean normal;
* ``uniform``: leaf name -> [low, high];
* any other leaf of two or more dimensions (after the stacked-layer axis)
  is a matrix and gets a normal with standard deviation 1/sqrt(fan_in).

A leaf that no rule covers is an error, so a new parameter of the program
cannot slip in with a made-up distribution.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.traffic import seed_words


def leaf_path(path) -> Tuple[str, ...]:
    out = []
    for k in path:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "idx"):
            out.append(str(k.idx))
        else:
            out.append(str(k))
    return tuple(out)


def weight_key(seed: int, salt: int = 0) -> jax.Array:
    """A JAX key from any non-negative seed (also those past 32 bits)."""
    words = np.random.SeedSequence(seed_words(seed, salt)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _rule(path: Tuple[str, ...], shape: Tuple[int, ...], init: dict
          ) -> Tuple[str, Any]:
    name = path[-1]
    if name in init.get("const", {}):
        return "const", float(init["const"][name])
    if name in init.get("std", {}):
        return "normal", float(init["std"][name])
    if name in init.get("uniform", {}):
        lo, hi = init["uniform"][name]
        return "uniform", (float(lo), float(hi))
    eff = shape[1:] if path[0] == "layers" else shape
    if len(eff) >= 2:
        return "normal", 1.0 / math.sqrt(eff[-2])
    raise KeyError(f"no init rule for parameter {'/'.join(path)} "
                   f"of shape {shape}")


def make_init_fn(shapes: Any, init: dict, dtype) -> Callable[[jax.Array], Any]:
    """``key -> params`` for the tree ``shapes``; jit it to make the
    weights on the device in one call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    rules: List[Tuple[Tuple[int, ...], str, Any]] = []
    for path, sds in flat:
        p = leaf_path(path)
        kind, arg = _rule(p, tuple(sds.shape), init)
        rules.append((tuple(sds.shape), kind, arg))

    def init_fn(key: jax.Array) -> Any:
        leaves = []
        for i, (shape, kind, arg) in enumerate(rules):
            k = jax.random.fold_in(key, i)
            if kind == "const":
                x = jnp.full(shape, arg, jnp.float32)
            elif kind == "normal":
                x = jax.random.normal(k, shape, jnp.float32) * arg
            else:
                x = jax.random.uniform(k, shape, jnp.float32, arg[0], arg[1])
            leaves.append(x.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return init_fn


def leaf_norms(tree: Any) -> Dict[str, jax.Array]:
    """Euclidean norm of every leaf; a stacked-layer leaf gives one norm per
    layer, named ``layers/<j>/.../name[<layer>]``."""
    out: Dict[str, jax.Array] = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        p = leaf_path(path)
        x = x.astype(jnp.float32)
        name = "/".join(p)
        if p[0] == "layers":
            n = jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim))))
            for layer in range(x.shape[0]):
                out[f"{name}[{layer}]"] = n[layer]
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x)))
    return out
