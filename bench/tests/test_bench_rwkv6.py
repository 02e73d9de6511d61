"""The rwkv6 configuration on the CPU at a small size: the program's
training steps (chunked WKV and, at SL 64, the sequential scan) agree with
the plain token-by-token reference, and the control fails."""
import copy

from bench.control import CONTROLS
from bench.lib import check
from bench.lib.harness import CHECKED_STEPS, Program
from bench.lib.traffic import make_epoch
from bench.lib.weights import weight_key
from bench.tests.tiny import cut, files


def _gaps(cfgspec, traffic, seed):
    epoch = make_epoch(traffic, cfgspec["model"]["vocab_size"], seed)
    checked = epoch.pick(CHECKED_STEPS)
    program = Program(cfgspec, traffic)
    prog = program.checked_steps(epoch, checked, seed)
    program.drop()
    ref = check.reference_readings(
        cfgspec, program.init_fn, weight_key(seed),
        [epoch.batch(i) for i in checked], pad_to=traffic["max_len"])
    return prog, check.gaps(prog, ref)


def test_program_agrees_with_the_reference(cpu_run):
    cfgspec, traffic = cut(*files("rwkv6-3b-2L", "lm-docs-random-g64"))
    for seed in (5, 2 ** 31 + 5):
        _, gaps = _gaps(cfgspec, traffic, seed)
        assert check.verdict(gaps, cfgspec["limits"]), gaps


def test_bfloat16_path_fails(cpu_run):
    cfgspec, traffic = cut(*files("rwkv6-3b-2L", "lm-docs-random-g64"))
    cfgspec = dict(copy.deepcopy(cfgspec), dtypes=dict(CONTROLS["control_bf16"]))
    _, gaps = _gaps(cfgspec, traffic, 5)
    assert not check.verdict(gaps, cfgspec["limits"])
