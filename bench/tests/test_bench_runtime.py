"""A configuration's optional ``runtime`` object reaches the program's train
step, and its optional ``reference_row_blocks`` gives the reference's
single-pass readings, at a CPU size."""
import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.control import CONTROLS
from bench.lib import check
from bench.lib.harness import CHECKED_STEPS, Program, build_trainer, run_cell
from bench.lib.traffic import make_epoch
from bench.lib.weights import weight_key
from bench.tests.tiny import cut, files

FAMILIES = {"sc2": ("starcoder2-3b-2L", "lm-docs-random-g16"),
            "rwkv6": ("rwkv6-3b-2L", "lm-docs-random-g64")}
CHECKPOINT = ("remat2", "checkpoint", "remat")   # jax.checkpoint's primitive


def _tiny(family, **extra):
    cfgspec, traffic = cut(*files(*FAMILIES[family]))
    cfgspec.update(copy.deepcopy(extra))
    return cfgspec, traffic


def _todays_run_config(cfgspec, traffic):
    """The ``RunConfig`` as the harness built it before ``runtime``."""
    from repro.configs import (
        MeshConfig,
        OptimizerConfig,
        RunConfig,
        ShapeConfig,
        StepKind,
    )
    from bench.lib.harness import _model_config

    o = cfgspec["optimizer"]
    return RunConfig(
        model=_model_config(cfgspec),
        shape=ShapeConfig("bench", seq_len=traffic["max_len"],
                          global_batch=traffic["batch"], step=StepKind.TRAIN),
        mesh=MeshConfig(shape=(1,), axes=("data",)),
        optimizer=OptimizerConfig(
            name=o["name"], lr=o["lr"], beta1=o["beta1"], beta2=o["beta2"],
            eps=o["eps"], weight_decay=o["weight_decay"],
            grad_clip=o["grad_clip"], warmup_steps=o["warmup_steps"]),
        param_dtype=cfgspec["dtypes"]["param"],
        compute_dtype=cfgspec["dtypes"]["compute"])


@pytest.mark.parametrize("runtime", [None, {}, {"remat": "none"}],
                         ids=["absent", "empty", "remat-none"])
@pytest.mark.parametrize("config,traffic", [
    ("starcoder2-3b-2L", "lm-docs-random-g16"),
    ("rwkv6-3b-2L", "lm-docs-random-g64")])
def test_default_runtime_builds_todays_run_config(config, traffic, runtime):
    cfgspec, traffic = files(config, traffic)
    if runtime is not None:
        cfgspec = dict(cfgspec, runtime=runtime)
    run = build_trainer(cfgspec, traffic, None).run
    today = _todays_run_config(cfgspec, traffic)
    for f in dataclasses.fields(today):
        assert getattr(run, f.name) == getattr(today, f.name), f.name
    assert run.remat == "none"


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _step_eqns(cfgspec, traffic):
    from repro.train.train_step import init_train_state

    trainer = build_trainer(cfgspec, traffic, None)
    state = jax.eval_shape(
        lambda r: init_train_state(trainer.model, trainer.run, r),
        jax.random.PRNGKey(0))
    shape = (traffic["batch"], traffic["max_len"])
    batch = {"tokens": jax.ShapeDtypeStruct(shape, jnp.int32),
             "labels": jax.ShapeDtypeStruct(shape, jnp.int32)}
    return list(_eqns(jax.make_jaxpr(trainer.step_fn)(state, batch).jaxpr))


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_runtime_reaches_the_train_step(family):
    cfgspec, traffic = _tiny(family)
    plain = _step_eqns(cfgspec, traffic)
    assert not any(e.primitive.name in CHECKPOINT for e in plain)

    cfgspec["runtime"] = {"remat": "block"}
    remat = _step_eqns(cfgspec, traffic)
    assert any(e.primitive.name in CHECKPOINT for e in remat)


@pytest.mark.parametrize("extra,words", [
    ({"runtime": {"remat": "full"}}, "runtime.remat='full'"),
    ({"runtime": {"rematerialise": "block"}}, "unknown 'runtime' keys"),
    # the program's microbatched step weights microbatches alike, not by
    # their labelled positions, so no configuration may state it yet
    ({"runtime": {"microbatches": 2}}, "unknown 'runtime' keys"),
    ({"reference_row_blocks": 3}, "reference_row_blocks=3"),
    ({"reference_row_blocks": 0}, "reference_row_blocks=0"),
    ({"reference_row_blocks": True}, "reference_row_blocks=True"),
    ({"reference_row_blocks": 2.0}, "reference_row_blocks=2.0"),
    ({"reference_row_blocks": "2"}, "reference_row_blocks='2'"),
], ids=["remat-unknown", "key-unknown", "microbatches", "blocks-3",
        "blocks-0", "blocks-bool", "blocks-float", "blocks-str"])
def test_bad_values_raise_naming_the_configuration(extra, words):
    cfgspec, traffic = _tiny("sc2", **extra)
    with pytest.raises(ValueError) as e:
        Program(cfgspec, traffic)
    assert cfgspec["name"] in str(e.value) and words in str(e.value)


def _readings(cfgspec, traffic, seed):
    """(program, reference) readings of one seed's checked steps."""
    epoch = make_epoch(traffic, cfgspec["model"]["vocab_size"], seed)
    checked = epoch.pick(CHECKED_STEPS)
    program = Program(cfgspec, traffic)
    prog = program.checked_steps(epoch, checked, seed)
    program.drop()
    ref = check.reference_readings(
        cfgspec, program.init_fn, weight_key(seed),
        [epoch.batch(i) for i in checked], pad_to=traffic["max_len"])
    return prog, ref


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_remat_block_reads_correct(cpu_run, family):
    cfgspec, traffic = _tiny(family, runtime={"remat": "block"},
                             reference_row_blocks=2)
    prog, ref = _readings(cfgspec, traffic, 2 ** 31 + 7)
    gaps = check.gaps(prog, ref)
    assert check.verdict(gaps, cfgspec["limits"]), gaps


def test_whole_run_under_a_runtime_is_correct(cpu_run):
    cfgspec, traffic = _tiny("sc2", runtime={"remat": "block"},
                             reference_row_blocks=4)
    r = run_cell("sc2-train-random", 2 ** 33 + 1, 0.5, False,
                 require_chip=False, cfgspec=cfgspec, traffic=traffic,
                 log=lambda m: None)
    assert r["correct"] is True, r["checks"]


def _rel(a, b):
    return abs(a - b) / max(abs(b), 1e-30)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_row_blocks_give_the_single_pass_readings(cpu_run, family):
    seed = 2 ** 32 + 11
    cfgspec, traffic = _tiny(family)
    epoch = make_epoch(traffic, cfgspec["model"]["vocab_size"], seed)
    batches = [epoch.batch(i) for i in epoch.pick(CHECKED_STEPS)]
    # the blocks of a batch hold unequal numbers of labelled positions
    for blocks in (2, 4):
        counts = [np.sum(b.labels >= 0, axis=1).reshape(blocks, -1).sum(1)
                  for b in batches]
        assert any(len(set(c.tolist())) > 1 for c in counts), counts
    program = Program(cfgspec, traffic)

    def readings(spec, batches):
        return check.reference_readings(spec, program.init_fn,
                                        weight_key(seed), batches,
                                        pad_to=traffic["max_len"])

    out = {blocks: readings(dict(cfgspec, reference_row_blocks=blocks),
                            batches) for blocks in (1, 2, 4)}
    one = out[1]
    # AdamW's first update is about lr * sign(gradient), so elements whose
    # gradient is round-off move by the order in which it is summed: the
    # change norms of one pass over the same rows in reverse order say how
    # far they move by that alone; blocks may move them a few times as far
    reversed_rows = [dataclasses.replace(
        b, tokens=b.tokens[::-1].copy(), labels=b.labels[::-1].copy(),
        lens=b.lens[::-1].copy()) for b in batches]
    reordered = check.gaps(readings(cfgspec, reversed_rows),
                           one)["change_gap"]
    change_tol = max(1e-6, 4 * reordered)
    for blocks in (2, 4):
        got = out[blocks]
        for a, b in zip(got["losses"], one["losses"]):
            assert _rel(a, b) <= 1e-6, (blocks, a, b)
        for k, b in one["grad_norms"].items():
            assert _rel(got["grad_norms"][k], b) <= 1e-6, (blocks, k)
        # the change as the check reads it: worst leaf against the larger
        # of its norm and the median leaf's
        change = check.gaps(got, one)["change_gap"]
        assert change <= change_tol, (blocks, change, reordered)


@pytest.mark.parametrize("blocks", [2, "batch"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_faults_still_fail_with_row_blocks(cpu_run, family, blocks):
    """At 2 blocks and at one row a block, half the batch left out (planted
    in the reference) and the program's bfloat16 path both read not
    correct."""
    seed = 2 ** 31 + 21
    cfgspec, traffic = _tiny(family)
    cfgspec["reference_row_blocks"] = \
        traffic["batch"] if blocks == "batch" else blocks
    epoch = make_epoch(traffic, cfgspec["model"]["vocab_size"], seed)
    batches = [epoch.batch(i) for i in epoch.pick(CHECKED_STEPS)]
    init_fn = Program(cfgspec, traffic).init_fn
    ref, half = (check.reference_readings(
        cfgspec, init_fn, weight_key(seed), batches, rows=rows,
        pad_to=traffic["max_len"])
        for rows in (None, slice(0, traffic["batch"] // 2)))
    assert not check.verdict(check.gaps(half, ref), cfgspec["limits"])
    bf16 = dict(cfgspec, dtypes=dict(CONTROLS["control_bf16"]))
    ctl, ref = _readings(bf16, traffic, seed)
    assert not check.verdict(check.gaps(ctl, ref), cfgspec["limits"])
