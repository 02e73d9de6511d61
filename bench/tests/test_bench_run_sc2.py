"""A whole run of the starcoder2 cells on the CPU at a small size: the
timed path passes its check, and a broken step fails it."""
import pytest

from bench.lib.harness import run_cell
from bench.tests.tiny import tiny


@pytest.mark.parametrize("workload", ["sc2-train-random",
                                      "sc2-train-bucketed"])
def test_sound_run_is_correct(cpu_run, workload):
    cfgspec, traffic = tiny(workload)
    r = run_cell(workload, 2 ** 31 + 99, 0.5, False, require_chip=False,
                 cfgspec=cfgspec, traffic=traffic, log=lambda m: None)
    assert r["correct"] is True
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "checks"
    for name, c in r["checks"].items():
        assert c["value"] <= c["limit"], name


def _broken(monkeypatch, fault):
    """Plant ``fault`` in the program's train step, under the trainer."""
    import jax

    import repro.train.trainer as trainer_mod

    build = trainer_mod.build_train_step

    def broken_build(model, run, total_steps=10_000):
        step = build(model, run, total_steps)

        def broken(state, batch):
            if fault == "half_batch":
                half = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
                return step(state, half)
            _, metrics = step(state, batch)
            return state, metrics          # state returned unchanged

        return broken

    monkeypatch.setattr(trainer_mod, "build_train_step", broken_build)


@pytest.mark.parametrize("fault", ["half_batch", "state_unchanged"])
def test_broken_step_is_not_correct(cpu_run, monkeypatch, fault):
    _broken(monkeypatch, fault)
    cfgspec, traffic = tiny("sc2-train-random")
    r = run_cell("sc2-train-random", 17, 0.5, False, require_chip=False,
                 cfgspec=cfgspec, traffic=traffic, log=lambda m: None)
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for c in r["checks"].values())
