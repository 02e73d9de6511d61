"""Training traffic: documents of heavy-tailed length, batched and padded.

A traffic file of this kind fixes the dataset: document lengths (a
lognormal of the file's ``median`` and ``sigma`` in tokens, clipped to
``min`` and ``max_len``), the batching rule, the padding granularity and a
``dataset_seed``. The epoch's batches are planned from that seed alone, so
every run of a cell sees the same batches. The run's ``--seed`` shuffles
them inside consecutive blocks of ``shuffle_block`` batches, draws the
token ids and picks the batches whose steps are checked: two seeds run the
same sizes in another order, and the same seed gives the same inputs.
Small blocks keep the mix of lengths inside any stretch of the epoch the
same for every seed, so a window of fixed length does the same work
whatever the seed.

Batch planning follows the program's own ``repro.data`` generator
(``plan_epoch``), copied here so that the yardstick does not move when the
program does.
"""
from __future__ import annotations

from typing import List

import numpy as np

from bench.lib.traffic import Batch, pad_to, sample_tokens, seed_words


def doc_lengths(rng: np.random.RandomState, n: int, spec: dict) -> np.ndarray:
    """Lognormal lengths in tokens, clipped to [min, max_len]."""
    d = spec["doc_len"]
    ln = rng.lognormal(mean=np.log(d["median"]), sigma=d["sigma"], size=n)
    return np.clip(np.round(ln).astype(np.int64), d["min"], spec["max_len"])


def plan_batches(lens: np.ndarray, batch: int, batching: str,
                 rng: np.random.RandomState) -> List[np.ndarray]:
    """Members' lengths per batch. ``random``: shuffled, so a batch pads to
    the longest of a random draw. ``bucketed``: sorted by length, cut into
    batches, batch order shuffled."""
    if batching == "random":
        order = rng.permutation(len(lens))
    elif batching == "bucketed":
        order = np.argsort(lens, kind="stable")
    else:
        raise ValueError(f"unknown batching {batching!r}")
    lens = lens[order]
    n_full = len(lens) // batch * batch
    batches = lens[:n_full].reshape(-1, batch)
    if batching == "bucketed":
        batches = batches[rng.permutation(len(batches))]
    return [b.copy() for b in batches]


class Epoch:
    """The planned epoch of one traffic file, in the order a seed gives."""

    def __init__(self, spec: dict, vocab_size: int, seed: int):
        self.spec = spec
        self.vocab_size = vocab_size
        self.seed = seed
        plan_rng = np.random.RandomState(seed_words(spec["dataset_seed"]))
        lens = doc_lengths(plan_rng, spec["samples_per_epoch"], spec)
        members = plan_batches(lens, spec["batch"], spec["batching"],
                               plan_rng)
        order_rng = np.random.RandomState(seed_words(seed, 1))
        blk = spec["shuffle_block"]
        order = np.concatenate([
            start + order_rng.permutation(min(blk, len(members) - start))
            for start in range(0, len(members), blk)])
        self.members = [members[i] for i in order]
        self.padded = [min(pad_to(int(m.max()), spec["granularity"]),
                           spec["max_len"]) for m in self.members]

    def __len__(self) -> int:
        return len(self.members)

    def pick(self, n: int) -> List[int]:
        """``n`` distinct batch indices drawn from the seed."""
        rng = np.random.RandomState(seed_words(self.seed, 3))
        return [int(i) for i in rng.choice(len(self.members), n,
                                           replace=False)]

    def batch(self, i: int) -> Batch:
        """Batch ``i`` of the epoch (wrapping round), with its own tokens."""
        i %= len(self.members)
        sl, lens = self.padded[i], self.members[i]
        rng = np.random.RandomState(seed_words(self.seed, 2, i))
        toks = sample_tokens(rng, (len(lens), sl + 1), self.vocab_size,
                             self.spec["zipf_a"])
        mask = np.arange(sl + 1)[None, :] < lens[:, None] + 1
        toks = np.where(mask, toks, 0)
        labels = np.where(mask[:, 1:], toks[:, 1:], -1)
        return Batch(tokens=toks[:, :-1].astype(np.int32),
                     labels=labels.astype(np.int32), sl=sl, lens=lens)
