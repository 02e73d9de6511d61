"""The benchmark's traffic: a seed fixes the inputs, and only their order
and token ids change with it."""
import os

import numpy as np
import pytest

from bench.lib.traffic import make_epoch, seed_words
from bench.tests.tiny import files

CELLS = (("starcoder2-3b-2L", "lm-docs-random-g16"),
         ("rwkv6-3b-2L", "lm-docs-random-g64"),
         ("starcoder2-3b-2L", "lm-docs-bucketed-g16"))


def _epoch(cell, seed):
    cfgspec, traffic = files(*cell)
    return make_epoch(traffic, cfgspec["model"]["vocab_size"], seed)


@pytest.mark.parametrize("cell", CELLS)
def test_same_seed_same_batches(cell):
    seed = 2 ** 31 + 12345
    a, b = _epoch(cell, seed), _epoch(cell, seed)
    assert a.padded == b.padded
    for i in (0, 1, 57):
        x, y = a.batch(i), b.batch(i)
        assert np.array_equal(x.tokens, y.tokens)
        assert np.array_equal(x.labels, y.labels)
        assert x.sl == y.sl and np.array_equal(x.lens, y.lens)


@pytest.mark.parametrize("cell", CELLS)
def test_other_seed_same_sizes_other_order(cell):
    a, b = _epoch(cell, 7), _epoch(cell, 2 ** 33 + 7)
    assert a.padded != b.padded
    assert sorted(a.padded) == sorted(b.padded)
    blk = a.spec["shuffle_block"]
    # every block of batches holds the same padded lengths in both orders
    for s in range(0, len(a), blk):
        assert sorted(a.padded[s:s + blk]) == sorted(b.padded[s:s + blk])
    assert not np.array_equal(a.batch(0).tokens, b.batch(0).tokens) \
        or a.batch(0).sl != b.batch(0).sl
    assert a.pick(3) != b.pick(3) and a.pick(3) == a.pick(3)
    assert len(set(a.pick(3))) == 3


@pytest.mark.parametrize("cell", CELLS)
def test_batches_are_padded_documents(cell):
    ep = _epoch(cell, 3)
    g = ep.spec["granularity"]
    for i in range(0, len(ep), 97):
        b = ep.batch(i)
        assert b.sl % g == 0 and b.sl <= ep.spec["max_len"]
        assert b.tokens.shape == b.labels.shape == (ep.spec["batch"], b.sl)
        assert int((b.labels >= 0).sum()) == b.real_tokens
        assert b.tokens.min() >= 0 and b.tokens.max() < ep.vocab_size


def test_seed_words_cover_large_seeds():
    assert seed_words(5) == [5, 0]
    assert seed_words(2 ** 32 + 5) == [5, 1]
    with pytest.raises(ValueError):
        seed_words(-1)


def test_fit_of_document_lengths_by_hand(tmp_path):
    """The tool that fitted the traffic files' lengths measures whole
    functions, decorators and nested ones too, and skips test packages."""
    from bench.tools.fit_doc_lengths import fit, function_bytes, sources

    src = (b"import os\n"
           b"@dec\n"
           b"def f(x):\n"
           b"    def g():\n"
           b"        return 1\n"
           b"    return g\n")
    # f: lines 2-6 = 5 + 10 + 13 + 17 + 13 bytes; g: lines 4-5 = 13 + 17
    assert sorted(function_bytes(src)) == [30, 58]
    (tmp_path / "test").mkdir()
    (tmp_path / "test" / "t.py").write_bytes(src)
    (tmp_path / "m.py").write_bytes(src)
    assert [os.path.basename(p) for p in sources(str(tmp_path))] == ["m.py"]
    r = fit([100, 100, 400, 400], bytes_per_token=2.0)
    assert r["median_tokens"] == pytest.approx(100.0)
    assert r["sigma"] == pytest.approx(np.log(2.0))
    assert r["share_under_16_tokens"] == 0.0
