"""The control: the program's own bfloat16 path (parameters and compute in
bfloat16, the precision below the configuration's float32) run as the
timed path fails the check."""
import pytest

from bench.control import CONTROLS
from bench.lib.harness import run_cell
from bench.tests.tiny import tiny


@pytest.mark.parametrize("workload", ["sc2-train-random",
                                      "sc2-train-bucketed"])
def test_control_in_bfloat16_is_not_correct(cpu_run, workload):
    cfgspec, traffic = tiny(workload)
    cfgspec["dtypes"] = dict(CONTROLS["control_bf16"])
    r = run_cell(workload, 2 ** 32 + 3, 0.5, False, require_chip=False,
                 cfgspec=cfgspec, traffic=traffic, log=lambda m: None)
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for c in r["checks"].values())
