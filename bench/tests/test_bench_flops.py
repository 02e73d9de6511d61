"""Model FLOPs of both configurations against numbers worked by hand."""
import jax
import numpy as np
import pytest

from bench.lib import check
from bench.lib.harness import build_trainer
from bench.tests.tiny import files

CONFIGS = {"sc2": ("starcoder2-3b-2L", "lm-docs-random-g16"),
           "rwkv6": ("rwkv6-3b-2L", "lm-docs-random-g64")}


def _cfg(name):
    return files(*CONFIGS[name])[0]


def test_starcoder2_flops():
    cfg = _cfg("sc2")
    fam = check.family(cfg)
    # per layer: q 3072*3072 + k,v 2*3072*256 + o 3072*3072
    #            + wi 3072*24576 + wo 12288*3072 = 133,693,440
    # two layers + head 3072*49152 = 418,381,824
    assert fam.matmul_params(cfg["model"]) == 418_381_824
    # one document of 100 tokens: 3 * (2 * 418,381,824 * 100
    #   + 2 layers * 2 * 24 heads * 128 * 100 * 101)
    assert fam.train_flops(cfg["model"], [100]) == 251_401_420_800
    assert fam.train_flops(cfg["model"], [100, 100]) == 2 * 251_401_420_800


def test_rwkv6_flops():
    cfg = _cfg("rwkv6")
    fam = check.family(cfg)
    # per layer: r,k,v,g,o 5*2560^2 + decay 2*2560*64
    #            + channel 2*2560*8960 + 2560^2 = 85,524,480
    # two layers + head 2560*65536 = 338,821,120
    assert fam.matmul_params(cfg["model"]) == 338_821_120
    # one document of 100 tokens: 3 * 100 * (2 * 338,821,120
    #   + 2 layers * 4 * 2560 * 64)
    assert fam.train_flops(cfg["model"], [100]) == 203_685_888_000


@pytest.mark.parametrize("name,params", [("sc2", 569_392_128),
                                         ("rwkv6", 506_662_400)])
def test_counts_cover_the_programs_weights(name, params):
    """The counted matrices are the program's: all its parameters but the
    embedding table and the vectors."""
    cfg, traffic = files(*CONFIGS[name])
    shapes = jax.eval_shape(build_trainer(cfg, traffic, None).model.init,
                            jax.random.PRNGKey(0))
    mats = 0
    for path, x in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        name = str(path[-1].key)
        stacked = str(path[0].key) == "layers"
        if x.ndim - stacked >= 2 and name not in ("embed", "ln_w", "ln_b"):
            mats += int(np.prod(x.shape))
    assert mats == check.family(cfg).matmul_params(cfg["model"])
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) \
        == params
