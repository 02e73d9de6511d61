"""Where JAX keeps its persistent compilation cache.

The cache matches entries by a key that includes the directory, so it is
only ever found again when it stays at one path. Entry points call
``enable_compile_cache()`` before their first compile.
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                         "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Return the cache directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is changed here. Otherwise the cache goes to ``.jax_cache`` at
    the root of this checkout.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
