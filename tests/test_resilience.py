"""repro.resilience: fault plans, guards, retries, and trainer chaos paths
(rollback on NaN, preemption + resume parity, corrupt-checkpoint fallback,
serve deadlines/shedding)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import (
    MeshConfig,
    OptimizerConfig,
    RunConfig,
    ShapeConfig,
    StepKind,
    smoke_config,
)
from repro.data.batching import DataIterator
from repro.data.synthetic import IWSLT_LIKE
from repro.models import Runtime, build_model
from repro.resilience import (
    BatchSkipList,
    ClusterFailure,
    ClusterMonitor,
    DivergenceDetector,
    DivergenceError,
    FailureDomains,
    FaultPlan,
    FaultSpec,
    NonFiniteLossError,
    PeerHealthTracker,
    PeerLossFault,
    PreemptionFault,
    RecoveryPolicy,
    ReplicaSet,
    StepTimeWatchdog,
    TransientFault,
    backoff_delay,
    check_finite,
    faults,
    retry_with_backoff,
)
from repro.train.trainer import Trainer


@pytest.fixture(autouse=True)
def _no_global_faults():
    """Each test owns the global plan; none leaks to the next test."""
    prev = faults.install(None)
    yield
    faults.install(prev)


# -------------------------------------------------------------------------
# fault plans


def test_fault_spec_parsing():
    s = FaultSpec.parse("nan_loss@5:times=2")
    assert (s.point, s.step, s.times) == ("nan_loss", 5, 2)
    s = FaultSpec.parse("decode%0.25:times=3")
    assert (s.point, s.step, s.prob, s.times) == ("decode", None, 0.25, 3)
    s = FaultSpec.parse("straggler@3:delay=0.5")
    assert s.delay == 0.5
    s = FaultSpec.parse("peer_loss@7:host=2")
    assert (s.point, s.step, s.host) == ("peer_loss", 7, 2)
    s = FaultSpec.parse("peer_slow@4:host=1:delay=0.1")
    assert (s.host, s.delay) == (1, 0.1)
    with pytest.raises(ValueError):
        FaultSpec.parse("x@1:bogus=1")


def test_fault_plan_step_pinned_fires_once():
    plan = FaultPlan.parse("data_fetch@3")
    assert plan.check("data_fetch", 2) is None
    assert plan.check("data_fetch", 3) is not None
    assert plan.check("data_fetch", 3) is None       # times budget consumed
    assert plan.check("other_point", 3) is None


def test_fault_plan_probabilistic_is_deterministic():
    fires_a = [bool(FaultPlan.parse("decode%0.5:times=0").check("decode", i))
               for i in range(64)]
    fires_b = [bool(FaultPlan.parse("decode%0.5:times=0").check("decode", i))
               for i in range(64)]
    assert fires_a == fires_b                        # same seed -> same plan
    assert 8 < sum(fires_a) < 56                     # and it actually rolls
    fires_c = [bool(FaultPlan.parse("decode%0.5:times=0", seed=1)
                    .check("decode", i)) for i in range(64)]
    assert fires_a != fires_c                        # seed changes the draw


def test_fire_corrupt_delay_helpers():
    faults.install(FaultPlan.parse(
        "preempt@1,data_fetch@2,nan_loss@3,straggler@4:delay=0.75"))
    faults.fire("preempt", 0)                        # no-op off-schedule
    with pytest.raises(PreemptionFault):
        faults.fire("preempt", 1)
    with pytest.raises(TransientFault):
        faults.fire("data_fetch", 2)
    assert faults.corrupt("nan_loss", 2, 1.5) == 1.5
    assert np.isnan(faults.corrupt("nan_loss", 3, 1.5))
    assert faults.delay("straggler", 4) == 0.75
    assert faults.delay("straggler", 5) == 0.0


# -------------------------------------------------------------------------
# guards


def test_check_finite():
    assert check_finite(1.25) == 1.25
    with pytest.raises(NonFiniteLossError):
        check_finite(float("nan"), step=7)
    with pytest.raises(NonFiniteLossError):
        check_finite(float("inf"), name="grad_norm")


def test_divergence_detector_trips_on_sustained_spike():
    det = DivergenceDetector(ratio=3.0, patience=3, warmup=4)
    for i in range(10):
        det.update(1.0)
    det.update(10.0)
    det.update(10.0)
    with pytest.raises(DivergenceError):
        det.update(10.0)
    det.reset()
    det.update(10.0)                                 # fresh baseline, fine


def test_divergence_detector_tolerates_single_spike():
    det = DivergenceDetector(ratio=3.0, patience=3, warmup=4)
    for i in range(10):
        det.update(1.0)
    det.update(10.0)                                 # one bad step
    for i in range(10):
        det.update(1.0)                              # streak resets
    det.update(10.0)
    det.update(1.0)


def test_watchdog_per_sl_baseline_and_fallback():
    wd = StepTimeWatchdog(factor=3.0)
    assert wd.observe(64, 0.1).baseline is None      # cold start
    v = wd.observe(64, 0.1)
    assert v.baseline == pytest.approx(0.1) and not v.is_straggler
    assert wd.observe(64, 0.5).is_straggler          # 5x the SL-64 median
    # unseen SL falls back to the all-SL median
    v = wd.observe(128, 0.2)
    assert v.baseline is not None and not v.is_straggler


# -------------------------------------------------------------------------
# recovery primitives


def test_retry_with_backoff_succeeds_then_gives_up():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientFault("x", calls["n"])
        return "ok"

    assert retry_with_backoff(flaky, retries=3, base_delay=0.0) == "ok"
    assert calls["n"] == 3

    with pytest.raises(TransientFault):
        retry_with_backoff(lambda: (_ for _ in ()).throw(
            TransientFault("y", 0)), retries=2, base_delay=0.0)

    # preemption is not retryable
    def preempts():
        raise PreemptionFault("preempt", 0)

    with pytest.raises(PreemptionFault):
        retry_with_backoff(preempts, retries=5, base_delay=0.0)


def test_batch_skip_list():
    sl = BatchSkipList(skip_after=2)
    key = (0, 7)
    assert not sl.record_failure(key)
    assert not sl.should_skip(key)
    assert sl.record_failure(key)                    # second strike: poison
    assert sl.should_skip(key) and not sl.should_skip((0, 8))


def test_batch_skip_list_state_round_trip():
    sl = BatchSkipList(skip_after=2)
    sl.record_failure((0, 7))
    sl.record_failure((0, 7))
    sl.record_failure((1, 3))
    snap = sl.state()
    import json
    json.dumps(snap)                                 # must be JSON-able
    other = BatchSkipList(skip_after=2)
    other.restore(snap)
    assert other.poisoned == {(0, 7)}
    assert other.record_failure((1, 3))              # count carried over
    # merging an older snapshot never undoes in-memory poison status
    other.restore({"failures": [[[0, 7], 1]], "skip": []})
    assert other.poisoned == {(0, 7), (1, 3)}
    other.restore(None)                              # no-op
    assert other.poisoned == {(0, 7), (1, 3)}


def test_backoff_delay_cap_and_deterministic_jitter():
    # uncapped exponential would hit 0.02 * 2**9 = 10.24s; the cap holds
    d = backoff_delay(10, base_delay=0.02, factor=2.0, max_delay_s=2.0,
                      jitter_frac=0.0)
    assert d == 2.0
    # jitter stays within +/- frac and never exceeds the cap
    for attempt in range(1, 12):
        d = backoff_delay(attempt, base_delay=0.02, factor=2.0,
                          max_delay_s=2.0, jitter_frac=0.25, jitter_seed=0,
                          label="x")
        raw = min(0.02 * 2.0 ** (attempt - 1), 2.0)
        assert 0.75 * raw <= d <= min(1.25 * raw, 2.0)
    # deterministic per seed (chaos replay parity) ...
    a = backoff_delay(3, jitter_seed=7, label="ckpt_save")
    b = backoff_delay(3, jitter_seed=7, label="ckpt_save")
    assert a == b
    # ... but replicas with different seeds desynchronize
    spread = {backoff_delay(3, jitter_seed=s, label="ckpt_save")
              for s in range(16)}
    assert len(spread) > 8


# -------------------------------------------------------------------------
# trainer chaos paths


def _tiny_run(mesh_shape=(1,), mesh_axes=("data",)):
    cfg = smoke_config("starcoder2-3b").with_overrides(num_layers=2,
                                                       d_model=64, d_ff=128,
                                                       vocab_size=256)
    shape = ShapeConfig("tiny", seq_len=32, global_batch=8,
                        step=StepKind.TRAIN)
    mesh = MeshConfig(shape=mesh_shape, axes=mesh_axes)
    run = RunConfig(model=cfg, shape=shape, mesh=mesh,
                    optimizer=OptimizerConfig(lr=1e-3, warmup_steps=2),
                    param_dtype="float32", compute_dtype="float32")
    return cfg, run


class FakeClock:
    """Deterministic timer: one tick per call, so every measured step takes
    exactly 1.0 'seconds' and runtimes are bit-identical across runs."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def _make_trainer(tmp_path, *, ckpt_every=4, total=16, timer=None,
                  policy=None, mesh_shape=(1,)):
    cfg, run = _tiny_run(mesh_shape=mesh_shape)
    model = build_model(cfg, Runtime.from_run(run))
    data = DataIterator(IWSLT_LIKE, samples_per_epoch=256, batch_size=8,
                        vocab_size=cfg.vocab_size, granularity=8, seed=1)
    kw = {"timer": timer} if timer is not None else {}
    return Trainer(model, run, data, ckpt_dir=str(tmp_path),
                   ckpt_every=ckpt_every, total_steps=total,
                   policy=policy or RecoveryPolicy(backoff_base_s=0.0),
                   **kw)


def test_nan_loss_triggers_rollback_and_training_converges(tmp_path):
    faults.install(FaultPlan.parse("nan_loss@5"))
    tr = _make_trainer(tmp_path / "ck")
    rep = tr.train(12)
    assert rep.rollbacks == 1 and rep.guard_violations == 1
    assert rep.steps == 12 and len(rep.losses) == 12
    assert all(np.isfinite(rep.losses))              # poisoned step replayed
    assert np.mean(rep.losses[:4]) > np.mean(rep.losses[-4:])
    assert tr.epoch_log.num_iterations == 12


def test_persistent_nan_skips_poison_batch(tmp_path):
    # the same step NaNs twice: second rollback declares the batch poison
    # and training routes around it
    faults.install(FaultPlan.parse("nan_loss@5:times=2"))
    tr = _make_trainer(tmp_path / "ck")
    rep = tr.train(10)
    assert rep.rollbacks == 2
    assert rep.skipped_batches == 1
    assert rep.steps == 10 and len(rep.losses) == 10
    assert all(np.isfinite(rep.losses))


def test_guard_violation_without_ckpt_raises():
    cfg, run = _tiny_run()
    model = build_model(cfg, Runtime.from_run(run))
    data = DataIterator(IWSLT_LIKE, samples_per_epoch=256, batch_size=8,
                        vocab_size=cfg.vocab_size, granularity=8, seed=1)
    faults.install(FaultPlan.parse("nan_loss@2"))
    tr = Trainer(model, run, data)                   # no ckpt_dir: no net
    with pytest.raises(NonFiniteLossError):
        tr.train(5)


def test_data_fetch_fault_is_retried_transparently(tmp_path):
    faults.install(FaultPlan.parse("data_fetch@3"))
    tr = _make_trainer(tmp_path / "ck")
    rep = tr.train(8)
    assert rep.steps == 8 and len(rep.losses) == 8
    assert rep.rollbacks == 0                        # retry, not rollback


def test_preemption_resume_matches_fault_free_run_bitwise(tmp_path):
    steps = 12
    # fault-free reference with the deterministic clock
    ref = _make_trainer(tmp_path / "ref", timer=FakeClock())
    ref_rep = ref.train(steps)
    ref_sp = ref.seqpoints(error_threshold=0.1, n_threshold=32)

    # chaos run: transient loader fault, one NaN rollback, preemption at 9
    # with the emergency checkpoint silently corrupted, forcing restore to
    # fall back one step — the full acceptance gauntlet
    faults.install(FaultPlan.parse(
        "data_fetch@2,nan_loss@5,preempt@9,ckpt_corrupt@9"))
    ck = tmp_path / "ck"
    tr = _make_trainer(ck, timer=FakeClock())
    rep = tr.train(steps)
    assert rep.preempted and rep.steps == 9
    losses = list(rep.losses)
    pos = rep.steps
    resume_points = []
    for _ in range(4):                               # resume until complete
        if not rep.preempted and pos >= steps:
            break
        tr = _make_trainer(ck, timer=FakeClock())
        rep = tr.train(steps - pos)
        start = rep.resumed_from or 0
        resume_points.append(start)
        losses = losses[:start] + list(rep.losses)
        pos = start + rep.steps
    assert pos == steps

    # the corrupted emergency checkpoint (step 9) forced the first resume to
    # fall back to the step-8 periodic checkpoint
    assert resume_points[0] == 8
    np.testing.assert_allclose(losses, ref_rep.losses, rtol=1e-5, atol=1e-6)
    # EpochLog parity is bit-for-bit: same SLs, same (fake-clock) runtimes,
    # same wire-byte stats
    assert tr.epoch_log.to_jsonable() == ref.epoch_log.to_jsonable()
    sp = tr.seqpoints(error_threshold=0.1, n_threshold=32)
    assert sp.seq_lens == ref_sp.seq_lens
    np.testing.assert_array_equal(sp.weights, ref_sp.weights)
    assert (sp.k, sp.predicted, sp.actual) == \
        (ref_sp.k, ref_sp.predicted, ref_sp.actual)


def test_straggler_injection_is_flagged(tmp_path):
    faults.install(FaultPlan.parse("straggler@5:delay=1000"))
    tr = _make_trainer(tmp_path / "ck", timer=FakeClock())
    rep = tr.train(8)
    # fake clock: every step is 1.0s, the injected one 1001.0s
    assert rep.stragglers == 1
    assert rep.step_times[5] == pytest.approx(1001.0)


def test_divergence_guard_rolls_back_in_trainer(tmp_path):
    tr = _make_trainer(tmp_path / "ck")
    # hair-trigger detector fed a scripted loss spike at step 6
    tr.divergence = DivergenceDetector(ratio=1.5, patience=2, warmup=2)
    real_update = tr.divergence.update
    spiked = {"done": False}

    def scripted_update(loss, step=None):
        if step == 6 and not spiked["done"]:
            spiked["done"] = True
            real_update(loss * 100.0, step=step)
            real_update(loss * 100.0, step=step)
            return
        real_update(loss, step=step)

    tr.divergence.update = scripted_update
    rep = tr.train(10)
    assert rep.rollbacks >= 1
    assert rep.steps == 10


# -------------------------------------------------------------------------
# trainer: one step in flight


class _WatchedLoss:
    """A step's loss that logs when the trainer waits on it and reads it."""

    def __init__(self, x, i, log):
        self.x, self.i, self.log = x, i, log

    def block_until_ready(self):
        self.log.append(("wait", self.i))
        self.x.block_until_ready()
        return self

    def __float__(self):
        self.log.append(("read", self.i))
        return float(self.x)


class _Recorder:
    """Wraps a trainer's jitted step: logs each call's dispatch and the wait
    on and read of its loss, keeps each call's batch and the newest state."""

    def __init__(self, step_fn):
        self.step_fn = step_fn
        self.log = []
        self.batches = []
        self.state = None

    def __call__(self, state, batch):
        i = len(self.batches)
        self.batches.append({k: np.asarray(v) for k, v in batch.items()})
        self.log.append(("dispatch", i))
        self.state, metrics = self.step_fn(state, batch)
        return self.state, dict(metrics,
                                loss=_WatchedLoss(metrics["loss"], i,
                                                  self.log))


def _recorded_trainer(tmp_path, **kw):
    tr = _make_trainer(tmp_path, **kw)
    rec = tr.step_fn = _Recorder(tr.step_fn)
    return tr, rec


def test_pipelined_train_matches_the_step_run_in_order_bitwise(tmp_path):
    """Keeping a step in flight changes nothing the device computes: the
    losses and final parameters equal those of the jitted step called in
    order on the same batches, each waited on (across two checkpoint
    drains and the end)."""
    from repro.train.train_step import init_train_state

    tr, rec = _recorded_trainer(tmp_path / "ck")
    rep = tr.train(10)
    state = init_train_state(tr.model, tr.run,
                             jax.random.PRNGKey(tr.run.seed))
    losses = []
    for b in rec.batches:
        state, metrics = rec.step_fn(
            state, {k: jnp.asarray(v) for k, v in b.items()})
        losses.append(float(metrics["loss"]))
    assert len(losses) == 10 and rep.losses == losses
    for got, want in zip(jax.tree.leaves(rec.state), jax.tree.leaves(state)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_next_step_is_dispatched_before_the_previous_loss_is_read(tmp_path):
    """Step i+1 is queued before the host waits on step i, except where a
    checkpoint (every 4 steps) needs step i settled first."""
    tr, rec = _recorded_trainer(tmp_path / "ck")
    tr.train(10)
    at = {ev: k for k, ev in enumerate(rec.log)}
    assert len(at) == len(rec.log) == 30       # each event once
    for i in range(9):
        if (i + 1) % 4:
            assert at[("dispatch", i + 1)] < at[("wait", i)] \
                < at[("read", i)], i
        else:
            assert at[("read", i)] < at[("dispatch", i + 1)], i
    assert rec.log[-2:] == [("wait", 9), ("read", 9)]


def test_guard_on_a_step_with_the_next_in_flight_rolls_back_and_replays(
        tmp_path):
    """Step 5's loss reads NaN while step 6 is in flight: step 6 is
    discarded, step 5's batch (not 6's) takes the failure, the rollback to
    step 4 replays batches 4, 5 and 6, and the EpochLog ends with the
    fault-free run's SLs."""
    ref = _make_trainer(tmp_path / "ref")
    ref.train(10)

    obs.metrics.reset()
    faults.install(FaultPlan.parse("nan_loss@5"))
    tr, rec = _recorded_trainer(tmp_path / "ck")
    rep = tr.train(10)
    assert rep.rollbacks == 1 and rep.guard_violations == 1
    assert rec.log.index(("dispatch", 6)) < rec.log.index(("read", 5))
    assert len(rec.batches) == 13
    for replayed, first in zip(rec.batches[7:10], rec.batches[4:7]):
        np.testing.assert_array_equal(replayed["tokens"], first["tokens"])
    assert tr.skiplist.state()["failures"] == [[[0, 5], 1]]
    assert list(tr.epoch_log.seq_lens()) == list(ref.epoch_log.seq_lens())
    assert len(rep.losses) == 10 and all(np.isfinite(rep.losses))
    drains = {r["labels"]["reason"]: r["value"] for r in
              obs.metrics.snapshot()["train_pipeline_drains_total"]}
    assert drains == {"checkpoint": 2, "guard": 1, "end": 1}
    obs.metrics.reset()


@pytest.mark.parametrize("spec, mesh_shape", [
    ("nan_loss@5,preempt@6", (1,)),
    ("nan_loss@5,peer_loss@5:host=2", (4,)),
])
def test_interruption_drain_that_trips_a_guard_rolls_back_first(
        tmp_path, spec, mesh_shape):
    """A preemption (or a confirmed peer loss) arrives at step 6 while step
    5, in flight, reads NaN: the drain rolls back to step 4, and the
    emergency checkpoint (or the re-mesh) then pins step 4, so the finished
    run logs the fault-free run's SLs."""
    ref = _make_trainer(tmp_path / "ref", mesh_shape=mesh_shape)
    ref.train(10)
    faults.install(FaultPlan.parse(spec))
    ck = tmp_path / "ck"
    tr = _make_trainer(ck, mesh_shape=mesh_shape)
    rep = tr.train(10)
    assert rep.rollbacks == 1
    assert tr.skiplist.state()["failures"] == [[[0, 5], 1]]
    preempted = "preempt" in spec
    assert rep.preempted == preempted
    if preempted:
        assert rep.steps == 4
        tr = _make_trainer(ck, mesh_shape=mesh_shape)
        rep = tr.train(6)
        assert rep.resumed_from == 4 and rep.rollbacks == 0
    else:
        assert rep.remeshes == 1 and rep.steps == 10
    assert list(tr.epoch_log.seq_lens()) == list(ref.epoch_log.seq_lens())


class _FeedEnded(Exception):
    pass


class _EndingFeed:
    """A data iterator that raises after ``n`` batches, as a benchmark's
    feed does when its window closes."""

    def __init__(self, data, n):
        self.data, self.n = data, n

    def state(self):
        return self.data.state()

    def restore(self, state):
        self.data.restore(state)

    def __iter__(self):
        inner = iter(self.data)
        for _ in range(self.n):
            yield next(inner)
        raise _FeedEnded()


def test_exception_leaving_train_accepts_the_step_in_flight(tmp_path):
    obs.metrics.reset()
    tr = _make_trainer(tmp_path / "ck", ckpt_every=100)
    tr.data = _EndingFeed(tr.data, 5)
    with pytest.raises(_FeedEnded):
        tr.train(10)
    assert tr.epoch_log.num_iterations == 5
    snap = obs.metrics.snapshot()
    assert [(r["labels"], r["value"]) for r in
            snap["train_pipeline_drains_total"]] == \
        [({"reason": "exception"}, 1)]
    assert snap["train_steps_overlapped_total"][0]["value"] == 4
    obs.metrics.reset()


# -------------------------------------------------------------------------
# serve chaos paths


def _engine(**kw):
    cfg, run = _tiny_run()
    model = build_model(cfg, Runtime.from_run(run))
    params = model.init(jax.random.PRNGKey(0))
    from repro.serve.engine import ServeEngine
    return ServeEngine(model, params, batch_size=2, max_len=64,
                       sl_granularity=16, **kw)


def test_serve_tokens_out_counts_emitted_real_tokens():
    from repro.serve.engine import Request

    eng = _engine()
    reqs = [Request(prompt=np.arange(1, 9, dtype=np.int32),
                    max_new_tokens=5)]
    eng.run_batch(reqs)
    rec = eng.log.iterations[-1]
    # one real request, five tokens emitted — the padded dummy slot and the
    # requested-vs-emitted distinction must not inflate the count
    assert rec.stats["tokens_out"] == 5.0
    assert rec.stats["tokens_out"] == float(len(reqs[0].output))


def test_serve_sheds_overload_instead_of_crashing():
    from repro.serve.engine import Request

    eng = _engine()
    reqs = [Request(prompt=np.arange(1, 9, dtype=np.int32),
                    max_new_tokens=2) for _ in range(4)]
    out = eng.run_batch(reqs)
    assert out is reqs
    assert [r.shed for r in reqs] == [False, False, True, True]
    assert all(len(r.output) == 2 for r in reqs[:2])
    assert all(len(r.output) == 0 for r in reqs[2:])


def test_serve_deadline_curtails_decode():
    from repro.serve.engine import Request

    eng = _engine(deadline_s=0.0)                    # budget gone at once
    reqs = [Request(prompt=np.arange(1, 9, dtype=np.int32),
                    max_new_tokens=8)]
    eng.run_batch(reqs)
    # prefill's token is delivered; the deadline stops all decode calls
    assert len(reqs[0].output) == 1
    rec = eng.log.iterations[-1]
    assert rec.stats["decode_steps"] == 0.0
    assert rec.stats["tokens_out"] == 1.0


def test_serve_decode_fault_is_retried():
    from repro.serve.engine import Request

    faults.install(FaultPlan.parse("decode@1"))
    eng = _engine(policy=RecoveryPolicy(backoff_base_s=0.0))
    reqs = [Request(prompt=np.arange(1, 9, dtype=np.int32),
                    max_new_tokens=4)]
    eng.run_batch(reqs)
    assert len(reqs[0].output) == 4                  # fault was invisible


# -------------------------------------------------------------------------
# multi-host failure domains (resilience.elastic)


def test_failure_domains_mapping_and_shrink():
    mesh = MeshConfig(shape=(4, 2), axes=("data", "model"))
    dom = FailureDomains.from_mesh(mesh)             # one host per data row
    assert dom.num_hosts == 4 and dom.devices_per_host == 2
    assert dom.devices_of(0) == [0, 1]
    assert dom.devices_of(3) == [6, 7]
    assert [dom.host_of(d) for d in range(8)] == [0, 0, 1, 1, 2, 2, 3, 3]
    assert dom.surviving_devices([1]) == [0, 1, 4, 5, 6, 7]
    new_mesh, new_dom = dom.surviving_mesh([1])
    assert new_mesh.shape == (3, 2) and new_mesh.axes == ("data", "model")
    assert new_dom.num_hosts == 3
    # losing nobody is the identity
    same_mesh, same_dom = dom.surviving_mesh([])
    assert same_mesh == mesh and same_dom is dom
    with pytest.raises(ClusterFailure):
        dom.surviving_mesh([0, 1, 2, 3])             # nothing left


def test_failure_domains_coarser_hosts():
    mesh = MeshConfig(shape=(4, 2), axes=("data", "model"))
    dom = FailureDomains.from_mesh(mesh, num_hosts=2)  # 2 data rows / host
    assert dom.devices_of(1) == [4, 5, 6, 7]
    new_mesh, _ = dom.surviving_mesh([0])
    assert new_mesh.shape == (2, 2)
    with pytest.raises(ValueError):                  # 4 rows, 3 hosts
        FailureDomains.from_mesh(mesh, num_hosts=3)


def test_peer_health_tracker_confirms_after_misses():
    tk = PeerHealthTracker([0, 1, 2], confirm_misses=2)
    v = tk.observe({0, 2}, tick=0)                   # host 1 misses once
    assert v.suspect == {1} and not v.confirmed_lost
    v = tk.observe({0, 1, 2}, tick=1)                # late beat resets it
    assert not v.suspect and not v.confirmed_lost
    v = tk.observe({0, 2}, tick=2)
    v = tk.observe({0, 2}, tick=3)                   # second consecutive miss
    assert v.confirmed_lost == {1}
    tk.forget([1])
    assert tk.hosts == (0, 2)


def test_cluster_monitor_confirms_peer_loss():
    faults.install(FaultPlan.parse("peer_loss@3:host=1"))
    mon = ClusterMonitor.from_mesh(MeshConfig(shape=(4,), axes=("data",)))
    for t in range(3):
        mon.pulse(t)                                 # all healthy
    mon.pulse(3)                                     # first missed beat
    assert mon.healthy_hosts == (0, 2, 3)
    with pytest.raises(PeerLossFault) as ei:
        mon.pulse(4)                                 # second miss: confirmed
    assert ei.value.hosts == {1}
    survivor = mon.after_loss(ei.value.hosts)
    assert survivor.domains.mesh.shape == (3,)
    assert survivor.hosts == (0, 1, 2)               # renumbered


def test_cluster_monitor_peer_slow_is_not_a_loss():
    faults.install(FaultPlan.parse("peer_slow@3:host=1:delay=0.1"))
    mon = ClusterMonitor.from_mesh(MeshConfig(shape=(4,), axes=("data",)))
    for t in range(8):
        mon.pulse(t)                                 # one miss never confirms
    assert mon.healthy_hosts == (0, 1, 2, 3)


def test_cluster_monitor_partition_loses_far_side():
    faults.install(FaultPlan.parse("mesh_partition@2:host=2"))
    mon = ClusterMonitor.from_mesh(MeshConfig(shape=(4,), axes=("data",)))
    mon.pulse(0)
    mon.pulse(1)
    mon.pulse(2)                                     # hosts 2,3 cut off
    with pytest.raises(PeerLossFault) as ei:
        mon.pulse(3)
    assert ei.value.hosts == {2, 3}


def test_replica_set_strikes_and_picks():
    rs = ReplicaSet(3)
    assert rs.pick_primary() == 0
    rs.mark_slow(0)
    assert rs.pick_primary() == 1
    assert rs.pick_hedge(exclude=1) == 2
    rs.mark_ok(0)
    assert rs.strikes(0) == 0
    assert ReplicaSet(1).pick_hedge(exclude=0) is None
    with pytest.raises(ValueError):
        ReplicaSet(0)


# -------------------------------------------------------------------------
# trainer tier-4: elastic re-mesh


def test_elastic_remesh_preserves_seqpoint_selection(tmp_path):
    steps = 12
    ref = _make_trainer(tmp_path / "ref", timer=FakeClock(),
                        mesh_shape=(4,))
    ref_rep = ref.train(steps)
    ref_sp = ref.seqpoints(error_threshold=0.1, n_threshold=32)

    # host 2 dies at step 6; confirmed one pulse later; the trainer
    # checkpoints, shrinks the mesh to 3 hosts, and finishes in-process
    faults.install(FaultPlan.parse("peer_loss@6:host=2"))
    tr = _make_trainer(tmp_path / "ck", timer=FakeClock(), mesh_shape=(4,))
    rep = tr.train(steps)
    assert rep.remeshes == 1 and rep.lost_hosts == [2]
    assert not rep.preempted and rep.steps == steps
    assert tr.run.mesh.shape == (3,)                 # DP axis shrunk
    assert tr.cluster.hosts == (0, 1, 2)             # survivors renumbered
    np.testing.assert_allclose(rep.losses, ref_rep.losses,
                               rtol=1e-5, atol=1e-6)
    # per-iteration (SL, runtime) parity is exact — SeqPoint selection only
    # reads those — while dp_wire_bytes legitimately changes with DP degree
    assert [it.seq_len for it in tr.epoch_log.iterations] == \
        [it.seq_len for it in ref.epoch_log.iterations]
    assert [it.runtime for it in tr.epoch_log.iterations] == \
        [it.runtime for it in ref.epoch_log.iterations]
    sp = tr.seqpoints(error_threshold=0.1, n_threshold=32)
    assert sp.seq_lens == ref_sp.seq_lens
    np.testing.assert_array_equal(sp.weights, ref_sp.weights)


def test_elastic_remesh_without_ckpt_raises():
    cfg, run = _tiny_run(mesh_shape=(4,))
    model = build_model(cfg, Runtime.from_run(run))
    data = DataIterator(IWSLT_LIKE, samples_per_epoch=256, batch_size=8,
                        vocab_size=cfg.vocab_size, granularity=8, seed=1)
    faults.install(FaultPlan.parse("peer_loss@2:host=1"))
    tr = Trainer(model, run, data)                   # no ckpt: no tier 4
    with pytest.raises(PeerLossFault):
        tr.train(6)


def test_single_host_loss_is_cluster_failure(tmp_path):
    # a (1,) mesh has one failure domain; losing it cannot be re-meshed
    faults.install(FaultPlan.parse("peer_loss@2:host=0"))
    tr = _make_trainer(tmp_path / "ck")
    with pytest.raises(ClusterFailure):
        tr.train(6)


# -------------------------------------------------------------------------
# skip list survives preemption resume


def test_skiplist_survives_preemption_resume(tmp_path):
    # batch at step 5 is persistently poisoned (two NaNs), then a preemption
    # at step 8 forces a process restart: the resumed trainer must remember
    # the poison without paying the discovery rollbacks again
    faults.install(FaultPlan.parse("nan_loss@5:times=2,preempt@8"))
    ck = tmp_path / "ck"
    tr = _make_trainer(ck)
    rep = tr.train(12)
    assert rep.rollbacks == 2 and rep.skipped_batches == 1
    assert rep.preempted and rep.steps == 8
    poisoned = tr.skiplist.poisoned
    assert poisoned

    tr2 = _make_trainer(ck)
    rep2 = tr2.train(12 - rep.steps)
    assert tr2.skiplist.poisoned == poisoned         # restored from extra
    assert rep2.rollbacks == 0                       # no rediscovery
    assert rep2.steps == 12 - rep.steps and not rep2.preempted


# -------------------------------------------------------------------------
# serve: deadline/shed interplay and request hedging


def test_serve_deadline_only_checked_between_decode_steps():
    from repro.serve.engine import Request

    # zero budget, but the single requested token comes from prefill: it is
    # delivered because the deadline is only consulted between decode steps
    from repro import obs

    eng = _engine(deadline_s=0.0)
    reqs = [Request(prompt=np.arange(1, 9, dtype=np.int32),
                    max_new_tokens=1)]
    before = obs.metrics.counter("serve_deadline_exceeded_total").value
    eng.run_batch(reqs)
    after = obs.metrics.counter("serve_deadline_exceeded_total").value
    assert len(reqs[0].output) == 1
    assert eng.log.iterations[-1].stats["decode_steps"] == 0.0
    assert after == before                           # never even checked


def test_serve_shed_request_requeues_cleanly():
    from repro.serve.engine import Request

    eng = _engine()
    reqs = [Request(prompt=np.arange(1, 9, dtype=np.int32),
                    max_new_tokens=3) for _ in range(3)]
    eng.run_batch(reqs)
    assert reqs[2].shed and reqs[2].output == []     # empty: safe to requeue
    eng.run_batch([reqs[2]])
    assert not reqs[2].shed                          # admitted this time
    assert len(reqs[2].output) == 3


def _run_serve(n_replicas, plan, n_batches=10, max_new_tokens=8):
    from repro.serve.engine import Request

    faults.install(FaultPlan.parse(plan) if plan else None)
    eng = _engine(n_replicas=n_replicas, hedge_factor=3.0,
                  policy=RecoveryPolicy(backoff_base_s=0.0))
    lat = []
    all_reqs = []
    for _ in range(n_batches):
        reqs = [Request(prompt=np.arange(1, 9, dtype=np.int32),
                        max_new_tokens=max_new_tokens)]
        eng.run_batch(reqs)
        all_reqs.extend(reqs)
        lat.append(eng.log.iterations[-1].stats["latency_s"])
    return eng, lat, all_reqs


def test_hedged_serve_cuts_tail_latency():
    # the 9th execution runs on a degraded link: every decode call is 2.0s
    # late (virtually). Unhedged eats the full tail; hedged re-issues on the
    # healthy replica and commits the fast finisher.
    plan = "peer_slow@8:delay=2.0"
    _, unhedged, _ = _run_serve(1, plan)
    eng, hedged, reqs = _run_serve(2, plan)
    assert unhedged[8] > 10.0                        # 7 decode calls x 2.0s
    assert hedged[8] < unhedged[8] / 2
    assert np.percentile(hedged, 99) < np.percentile(unhedged, 99)
    rec = eng.log.iterations[8]
    assert rec.stats["hedged"] == 1.0
    assert rec.stats["replica"] == 1.0               # hedge replica won
    from repro import obs
    assert obs.metrics.counter("serve_hedges_total").value >= 1
    assert obs.metrics.counter("serve_hedge_wins_total").value >= 1
    assert eng.replicas.strikes(0) >= 1              # loser took a strike


def test_hedge_cancelled_tokens_never_reach_caller_or_counter():
    eng, _, reqs = _run_serve(2, "peer_slow@8:delay=2.0")
    # exactly max_new_tokens per request — a double-commit would show up as
    # 16 tokens on the hedged batch's request
    assert all(len(r.output) == 8 for r in reqs)
    assert all(it.stats["tokens_out"] == 8.0 for it in eng.log.iterations)
    assert sum(it.stats["tokens_out"] for it in eng.log.iterations) == 80.0


def test_unhedged_single_replica_never_hedges():
    eng, _, _ = _run_serve(1, "peer_slow@4:delay=2.0", n_batches=6)
    assert all(it.stats["hedged"] == 0.0 for it in eng.log.iterations)


# -------------------------------------------------------------------------
# env wiring


def test_env_spec_round_trip():
    plan = FaultPlan.parse(os.environ.get("X_UNSET", "") or
                           "nan_loss@5,preempt@9", seed=3)
    assert [s.point for s in plan.specs] == ["nan_loss", "preempt"]
    assert plan.seed == 3
