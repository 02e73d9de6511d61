"""Small configurations and traffic of the benchmark's cells, for the CPU.

The widths are cut so that a whole run (set-up, a one-second window and
the reference) takes seconds on the CPU; every cut key is listed in
``reduced``, as the harness requires.
"""
from __future__ import annotations

import copy

from bench.lib.harness import cell_files, load_json

TINY_MODEL = {
    "gqa_transformer": {"hidden_size": 64, "intermediate_size": 128,
                        "num_attention_heads": 4, "num_key_value_heads": 2,
                        "num_hidden_layers": 2, "vocab_size": 512},
    "rwkv6": {"hidden_size": 128, "intermediate_size": 256,
              "num_hidden_layers": 2, "vocab_size": 512, "head_size": 64},
}


def files(config: str, traffic: str):
    """A configuration file and a traffic file, found by name."""
    return (load_json(f"bench/configs/{config}.json"),
            load_json(f"bench/traffic/{traffic}.json"))


def tiny(workload: str):
    """(configuration, traffic) of ``workload`` cut to a CPU size."""
    _, _, cfgspec, traffic = cell_files(workload)
    return cut(cfgspec, traffic)


def cut(cfgspec: dict, traffic: dict):
    """A configuration and a traffic mix cut to a CPU size."""
    cfgspec, traffic = copy.deepcopy(cfgspec), copy.deepcopy(traffic)
    cut = TINY_MODEL[cfgspec["family"]]
    cfgspec["model"].update(cut)
    cfgspec["reduced"] = sorted(set(cfgspec["reduced"]) | set(cut))
    max_len = 2 * traffic["granularity"] * 2
    traffic.update(max_len=max_len, samples_per_epoch=256,
                   doc_len=dict(traffic["doc_len"], median=max_len / 4))
    return cfgspec, traffic
