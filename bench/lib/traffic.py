"""Seeded traffic: what every kind of traffic shares, and the lookup of a
traffic file's kind.

A traffic file names its ``kind``; the generator of that kind is
``bench/kinds/<kind>.py``, found by name, so a new kind of traffic is a
new file. Its ``Epoch(spec, vocab_size, seed)`` plans the file's batches
and offers ``len()``, ``padded`` (each batch's padded length), ``pick(n)``
(``n`` distinct batch indices drawn from the seed) and ``batch(i)`` (a
``Batch``). The same seed gives the same inputs.

Token ids follow the program's own ``repro.data`` generator
(``sample_tokens``), copied here so that the yardstick does not move when
the program does.
"""
from __future__ import annotations

import importlib.util
import os
from dataclasses import dataclass
from typing import List

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def seed_words(*parts: int) -> List[int]:
    """Any non-negative whole numbers as 32-bit words for ``RandomState``."""
    words: List[int] = []
    for p in parts:
        p = int(p)
        if p < 0:
            raise ValueError(f"seed parts must be non-negative, got {p}")
        words.extend([p & 0xFFFFFFFF, p >> 32])
    return words


def pad_to(sl: int, granularity: int) -> int:
    return int(-(-sl // granularity) * granularity)


def sample_tokens(rng: np.random.RandomState, shape, vocab_size: int,
                  zipf_a: float) -> np.ndarray:
    """Zipf-distributed token ids in [0, vocab)."""
    n = int(np.prod(shape))
    ranks = rng.zipf(zipf_a, size=n).astype(np.int64)
    return (np.minimum(ranks, vocab_size) - 1).reshape(shape)


@dataclass(frozen=True)
class Batch:
    tokens: np.ndarray      # (batch, sl) int32, 0 after each document
    labels: np.ndarray      # (batch, sl) int32, -1 after each document
    sl: int                 # padded sequence length
    lens: np.ndarray        # documents' lengths

    @property
    def real_tokens(self) -> int:
        return int(self.lens.sum())


def make_epoch(spec: dict, vocab_size: int, seed: int, root: str = ROOT):
    """The epoch of traffic file ``spec``, by its kind's generator."""
    kind = spec["kind"]
    path = os.path.join(root, "bench", "kinds", kind + ".py")
    if not os.path.exists(path):
        raise KeyError(f"no generator bench/kinds/{kind}.py for the "
                       f"traffic kind {kind!r}")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_kind_" + kind.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.Epoch(spec, vocab_size, seed)
