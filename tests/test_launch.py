"""Entry-point plumbing: the launcher's trainer, the compile-cache
location, and results that must name the device that produced them."""
import json
import math
import os
import sys

import jax
import pytest

from repro.configs import smoke_config
from repro.core import reproduction
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import build_trainer

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_launcher_trainer_trains_and_logs_sls():
    cfg = smoke_config("starcoder2-3b").with_overrides(vocab_size=256)
    trainer = build_trainer(cfg, seq=64, batch=4, steps=3)
    rep = trainer.train(3)
    assert len(rep.losses) == 3
    assert all(math.isfinite(x) for x in rep.losses)
    assert trainer.epoch_log.num_iterations == 3
    assert all(16 <= s <= 64 and s % 16 == 0
               for s in trainer.epoch_log.seq_lens())
    assert trainer.seqpoints().num_points >= 1


def test_compile_cache_leaves_env_var_to_jax(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_dir_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first, second = enable_compile_cache(), enable_compile_cache()
        assert first == second == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_reproduction_never_returns_another_devices_result(monkeypatch,
                                                           tmp_path):
    monkeypatch.setattr(reproduction, "RESULTS_DIR", str(tmp_path))
    here = reproduction.cache_path("gnmt")
    this_device = reproduction._device
    monkeypatch.setattr(reproduction, "_device", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    elsewhere = reproduction.cache_path("gnmt")
    monkeypatch.setattr(reproduction, "_device", this_device)
    assert here != elsewhere
    # results measured elsewhere, keyed and (as older runs wrote) unkeyed
    for path in (elsewhere, os.path.join(str(tmp_path), "repro_gnmt.json")):
        with open(path, "w") as f:
            json.dump({"network": "gnmt", "stale": True}, f)

    class Ran(Exception):
        pass

    def measure():
        raise Ran

    monkeypatch.setitem(reproduction.SETUPS, "gnmt", measure)
    with pytest.raises(Ran):
        reproduction.run_reproduction("gnmt")

    with open(here, "w") as f:
        json.dump({"network": "gnmt", "mine": True}, f)
    assert reproduction.run_reproduction("gnmt") == {"network": "gnmt",
                                                     "mine": True}


@pytest.mark.parametrize("raises", [False, True])
def test_bench_runner_exit_status(monkeypatch, capsys, raises):
    monkeypatch.syspath_prepend(REPO)
    from benchmarks import padding_waste
    from benchmarks import run as bench_run

    def probe_bench(fast):
        if raises:
            raise RuntimeError("broken")
        print("probe_bench,0.000,ok")

    monkeypatch.setattr(padding_waste, "ALL", [probe_bench])
    monkeypatch.setattr(sys, "argv", ["run", "--only", "probe_bench"])
    assert bench_run.main() == (1 if raises else 0)
    out = capsys.readouterr().out
    assert ("BENCH_ERROR_probe_bench" in out) == raises
