"""Training loop: auto-resume, async checkpoints, straggler detection,
SeqPoint epoch logging as a first-class hook — hardened for fleet faults.

The trainer logs every iteration's (padded SL, wallclock) into an
``EpochLog`` — after one epoch, ``seqpoints()`` hands back the
representative iterations, which is how a fleet user would profile a new
hardware/software config for this exact (model, dataset, batch-size)
combination without re-running the epoch (paper §V-C step 1 integrated at
the point the data already flows).

That projection is only trustworthy if the log survives real fleet
conditions, so the step loop is wrapped in a recovery ladder
(``repro.resilience``):

* transient data/checkpoint faults retry with backoff;
* a NaN/inf or diverging loss rolls back to the last good checkpoint —
  restoring params, optimizer, data-iterator position *and* the partial
  EpochLog — and a batch that fails repeatedly is skipped as poison;
* a preemption writes an emergency checkpoint pointing at the interrupted
  batch, so the resumed process replays it and the stitched EpochLog (and
  hence ``select_seqpoints``) matches the fault-free run bit-for-bit;
* a confirmed peer loss (``resilience.elastic``) checkpoints, shrinks the
  mesh over the surviving hosts, re-shards the restored state, and resumes
  in-process — the fourth recovery tier;
* a per-SL running-median watchdog flags stragglers (and injected ones).

The loop keeps one step in flight. Iteration *i* fetches batch *i* and
dispatches step *i* on the (not yet ready) state step *i−1* returned, and
only then waits for step *i−1*'s loss, runs the guards on it and accepts
it, so the device has the next step queued while the host does its
bookkeeping. The loop drains — waits for the step in flight and accepts it
before dispatching another — where the host needs the state or the step
settled: at a periodic checkpoint (the snapshot must precede the next
step's donation), at the end of ``train()``, on a preemption or a peer
loss, and on any exception that leaves ``train()``. A guard violation found
on step *i−1* discards step *i* and rolls back as above, charging step
*i−1*'s batch. Each step's ``dt`` (``EpochLog``, watchdog,
``report.step_times``) is completion to completion: the timer is read when
the wait on a step returns, and once more at dispatch when nothing was in
flight. With the device the bottleneck, that is the step's device time.
The ``train_steps_overlapped_total`` and ``train_pipeline_drains_total``
(``reason``) counters show how often the overlap engaged.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import jax
import numpy as np

from repro import obs
from repro.ckpt.checkpoint import CheckpointManager
from repro.configs.base import RunConfig
from repro.core.profile import EpochLog
from repro.dist.compression import dp_grad_wire_bytes
from repro.dist.sharding import tp_activation_wire_bytes
from repro.core.seqpoint import SeqPointSet, select_seqpoints
from repro.data.batching import DataIterator
from repro.models.model_zoo import Model
from repro.resilience import elastic, faults
from repro.resilience.elastic import ClusterMonitor, PeerLossFault
from repro.resilience.guards import (
    DivergenceDetector,
    GuardViolation,
    StepTimeWatchdog,
    check_finite,
)
from repro.resilience.faults import PreemptionFault, TransientFault
from repro.resilience.recovery import (
    BatchSkipList,
    RecoveryPolicy,
    pack_train_extra,
    retry_with_backoff,
    unpack_train_extra,
)
from repro.train.train_step import TrainState, build_train_step, \
    init_train_state


@dataclass
class TrainerReport:
    steps: int = 0
    resumed_from: Optional[int] = None
    losses: list = field(default_factory=list)
    step_times: list = field(default_factory=list)
    stragglers: int = 0
    epoch_log: Optional[EpochLog] = None
    # resilience accounting
    preempted: bool = False          # train() returned early; resume to finish
    rollbacks: int = 0
    guard_violations: int = 0
    skipped_batches: int = 0
    remeshes: int = 0                # tier-4 elastic re-meshes taken
    lost_hosts: list = field(default_factory=list)


@dataclass
class _InFlight:
    """A dispatched step whose loss the host has not read back yet."""
    step: int
    key: Tuple[int, int]             # (epoch, batch_index) of its batch
    sl: int
    metrics: Dict[str, jax.Array]
    dp_bytes: float
    tp_bytes: float


class Trainer:
    def __init__(self, model: Model, run: RunConfig, data: DataIterator, *,
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
                 straggler_factor: float = 3.0, total_steps: int = 1000,
                 policy: Optional[RecoveryPolicy] = None,
                 cluster: Optional[ClusterMonitor] = None,
                 timer: Callable[[], float] = time.perf_counter):
        self.model = model
        self.run = run
        self.data = data
        self.ckpt = CheckpointManager(ckpt_dir) if ckpt_dir else None
        self.ckpt_every = ckpt_every
        self.policy = policy or RecoveryPolicy()
        self.cluster = cluster or ClusterMonitor.from_mesh(run.mesh)
        self.skiplist = BatchSkipList(
            skip_after=self.policy.skip_after_failures)
        self.timer = timer
        self.watchdog = StepTimeWatchdog(factor=straggler_factor)
        self.divergence = DivergenceDetector(
            ratio=self.policy.divergence_ratio,
            patience=self.policy.divergence_patience)
        self.step_fn = jax.jit(build_train_step(model, run, total_steps),
                               donate_argnums=0)
        self.epoch_log = EpochLog(meta={"model": run.model.name})
        self._t_last = 0.0           # timer reading that starts the next dt

    # ------------------------------------------------------------------
    def _extra(self, step: int) -> dict:
        return pack_train_extra(step, self.data.state(), self.epoch_log,
                                self.skiplist)

    def _retry(self, fn, label: str):
        return retry_with_backoff(
            fn, retries=self.policy.max_retries,
            base_delay=self.policy.backoff_base_s,
            factor=self.policy.backoff_factor,
            max_delay_s=self.policy.max_delay_s,
            jitter_frac=self.policy.jitter_frac,
            jitter_seed=self.policy.jitter_seed, label=label)

    def init_or_resume(self, rng: jax.Array) -> tuple[TrainState, int]:
        state = init_train_state(self.model, self.run, rng)
        start = 0
        if self.ckpt is not None and self.ckpt.latest_step() is not None:
            state, extra = self._retry(lambda: self.ckpt.restore(state),
                                       label="ckpt_restore")
            start, data_state, log, skip_state = unpack_train_extra(extra)
            if data_state is not None:
                self.data.restore(data_state)
            if log is not None:
                self.epoch_log = log
            # a poison batch stays poison across process restarts — the
            # resumed process must not pay the discovery rollbacks again
            self.skiplist.restore(skip_state)
        return state, start

    def _comm_profile(self, state: TrainState) -> Tuple[int, int, float]:
        """(dp_degree, tp_degree, per-step DP grad wire bytes) for the
        *current* mesh — recomputed after an elastic re-mesh shrinks DP."""
        dp_deg = self.run.mesh.num_devices \
            if self.run.parallelism == "dp_only" else self.run.mesh.data_degree
        tp_deg = self.run.mesh.model_degree \
            if self.run.parallelism == "tp" else 1
        dp_bytes = dp_grad_wire_bytes(
            state.params, self.run.optimizer.grad_compression, dp_deg)
        return dp_deg, tp_deg, dp_bytes

    # ------------------------------------------------------------------
    def train(self, num_steps: int, rng: Optional[jax.Array] = None
              ) -> TrainerReport:
        rng = jax.random.PRNGKey(self.run.seed) if rng is None else rng
        state, start = self.init_or_resume(rng)
        report = TrainerReport(resumed_from=start or None)
        it: Iterator = iter(self.data)
        # per-step DP gradient wire bytes are SL-independent (one param-sized
        # all-reduce); TP activation bytes scale with SL — both go into
        # EpochLog.stats so SeqPoint projects communication alongside compute
        dp_deg, tp_deg, dp_bytes = self._comm_profile(state)
        obs.event("train_start", model=self.run.model.name, start_step=start,
                  num_steps=num_steps, dp_degree=dp_deg, tp_degree=tp_deg)
        mreg = obs.metrics
        skiplist = self.skiplist
        end = start + num_steps
        step = start                  # the next step to dispatch
        # the dispatched step not yet accepted; ``state`` is its output
        pending: Optional[_InFlight] = None
        # rollback safety net: guarantee a restorable checkpoint exists
        # before the first optimizer step can fail
        if self.ckpt is not None and self.ckpt.latest_step() is None:
            self._retry(lambda: self.ckpt.save(start, state,
                                               extra=self._extra(start)),
                        label="ckpt_save")
            obs.event("checkpoint", step=start, mode="initial")
        try:
            while step < end or pending is not None:
                nxt = None
                try:
                    if pending is not None and (step == end or (
                            self.ckpt is not None
                            and step % self.ckpt_every == 0)):
                        # the periodic save snapshots the state, so it runs
                        # before the next step donates it
                        self._drain(pending, "end" if step == end
                                    else "checkpoint", report)
                        pending = None
                        if step == end:
                            break
                        self._save_periodic(step, state)
                    with obs.span("train/pulse"):
                        # iterator position BEFORE the fetch: the identity
                        # of the batch about to run, and the resume point if
                        # this step is preempted
                        pre_fetch = self.data.state()
                        batch_key = (pre_fetch["epoch"],
                                     pre_fetch["batch_index"])
                        skip = skiplist.should_skip(batch_key)
                        if not skip:
                            # heartbeat interval: raises PeerLossFault once
                            # the tracker confirms a host lost (tier-4
                            # re-mesh arm)
                            self.cluster.pulse(step)
                    if skip:
                        next(it)                      # discard poison batch
                        report.skipped_batches += 1
                        mreg.counter("train_skipped_batches_total").inc()
                        obs.event("poison_batch_skipped", step=step,
                                  epoch=batch_key[0],
                                  batch_index=batch_key[1])
                        continue
                    with obs.step_span("train/step", step) as step_span:
                        with obs.span("train/data_fetch"):
                            def fetch():
                                faults.fire("data_fetch", step)
                                return next(it)
                            tokens, labels, sl = self._retry(
                                fetch, label="data_fetch")
                            with obs.span("train/h2d"):
                                batch = {
                                    "tokens": jax.numpy.asarray(tokens),
                                    "labels": jax.numpy.asarray(labels)}
                        step_span.set(sl=sl)
                        faults.fire("preempt", step)
                        if pending is None:
                            self._t_last = self.timer()
                        else:
                            mreg.counter("train_steps_overlapped_total").inc()
                        with obs.span("train/step_fn", sl=sl):
                            state, metrics = self.step_fn(state, batch)
                        nxt = _InFlight(step, batch_key, sl, metrics, dp_bytes,
                                        tp_activation_wire_bytes(
                                            self.run.model,
                                            self.run.shape.global_batch, sl,
                                            tp_deg))
                        if pending is not None:
                            loss, dt = self._settle(pending)
                    if pending is not None:
                        self._accept(pending, loss, dt, report)
                    pending, step = nxt, step + 1
                except PreemptionFault:
                    flight, pending = pending, None
                    if flight is not None:
                        try:
                            self._drain(flight, "preempt", report)
                        except GuardViolation as e:
                            state, step = self._guard_rollback(
                                e, flight, state, start, report)
                            pre_fetch = self.data.state()
                    return self._handle_preemption(step, start, state,
                                                   pre_fetch, report)
                except PeerLossFault as e:
                    mreg.counter("train_peer_losses_total").inc(len(e.hosts))
                    obs.event("peer_lost", step=step, hosts=sorted(e.hosts),
                              tick=e.tick)
                    if self.ckpt is None \
                            or report.remeshes >= self.policy.max_remeshes:
                        raise
                    flight, pending = pending, None
                    if flight is not None:
                        try:
                            self._drain(flight, "remesh", report)
                        except GuardViolation as g:
                            state, step = self._guard_rollback(
                                g, flight, state, start, report)
                    state, step = self._remesh(e, step, start, state, report)
                    dp_deg, tp_deg, dp_bytes = self._comm_profile(state)
                    it = iter(self.data)  # regenerate from restored position
                except GuardViolation as e:
                    failed, pending = pending, None
                    if nxt is not None:
                        # the step dispatched on the failed one's output
                        mreg.counter("train_pipeline_drains_total",
                                     reason="guard").inc()
                    state, step = self._guard_rollback(e, failed, state,
                                                       start, report)
                    it = iter(self.data)  # regenerate from restored position
        except BaseException:
            # no step may be left running unrecorded (the feed may end the
            # run by raising, as a benchmark window does)
            if pending is not None:
                self._drain(pending, "exception", report)
            raise
        if self.ckpt is not None:
            with obs.span("train/checkpoint_final", step=end):
                self._wait_ckpt()
                self._retry(lambda: self.ckpt.save(end, state,
                                                   extra=self._extra(end)),
                            label="ckpt_save")
            obs.event("checkpoint", step=end, mode="final")
        report.steps = num_steps
        report.epoch_log = self.epoch_log
        obs.event("train_end", steps=num_steps, stragglers=report.stragglers,
                  rollbacks=report.rollbacks,
                  skipped_batches=report.skipped_batches,
                  total_runtime=self.epoch_log.total_runtime)
        return report

    # ------------------------------------------------------------------
    def _settle(self, f: _InFlight) -> Tuple[float, float]:
        """Wait for step ``f``, read its loss back and run the guards.

        Returns (loss, dt): dt runs from the previous step's completion, or
        from ``f``'s dispatch when nothing was in flight before it."""
        with obs.span("train/block_until_ready"):
            jax.block_until_ready(f.metrics["loss"])
        t = self.timer()
        dt = t - self._t_last + faults.delay("straggler", f.step)
        self._t_last = t
        with obs.span("train/readback"):
            loss = faults.corrupt("nan_loss", f.step,
                                  float(f.metrics["loss"]))
            check_finite(loss, name="loss", step=f.step)
            if self.policy.check_grads and "grad_norm" in f.metrics:
                check_finite(float(f.metrics["grad_norm"]),
                             name="grad_norm", step=f.step)
            self.divergence.update(loss, step=f.step)
        return loss, dt

    def _accept(self, f: _InFlight, loss: float, dt: float,
                report: TrainerReport) -> None:
        mreg = obs.metrics
        with obs.span("train/accept"):
            verdict = self.watchdog.observe(f.sl, dt)
            if verdict.is_straggler:
                report.stragglers += 1
                mreg.counter("train_stragglers_total").inc()
                obs.event("straggler", step=f.step, sl=f.sl, dt=dt,
                          baseline=verdict.baseline,
                          factor=self.watchdog.factor)
            report.losses.append(loss)
            report.step_times.append(dt)
            self.epoch_log.append(f.sl, dt, dp_wire_bytes=f.dp_bytes,
                                  tp_wire_bytes=f.tp_bytes)
            mreg.counter("train_steps_total").inc()
            mreg.histogram("train_step_time_s", sl=f.sl).observe(dt)

    def _drain(self, f: _InFlight, reason: str,
               report: TrainerReport) -> None:
        """Settle and accept the step in flight with nothing queued behind
        it: the host needs the state, or the loop is ending."""
        obs.metrics.counter("train_pipeline_drains_total",
                            reason=reason).inc()
        with obs.span("train/drain", reason=reason, step=f.step):
            loss, dt = self._settle(f)
            self._accept(f, loss, dt, report)

    def _guard_rollback(self, e: GuardViolation, f: _InFlight,
                        like: TrainState, start: int, report: TrainerReport
                        ) -> Tuple[TrainState, int]:
        """A guard tripped on step ``f``: count a failure against its batch
        and roll back, or re-raise when no rollback is left. ``like`` is the
        newest dispatched output, the one state not yet donated."""
        report.guard_violations += 1
        obs.metrics.counter("train_guard_violations_total").inc()
        obs.event("guard_violation", step=f.step, error=str(e),
                  epoch=f.key[0], batch_index=f.key[1])
        if self.ckpt is None or report.rollbacks >= self.policy.max_rollbacks:
            raise e
        report.rollbacks += 1
        poison = self.skiplist.record_failure(f.key)
        return self._rollback(like, start, report, poison=poison)

    # ------------------------------------------------------------------
    def _wait_ckpt(self) -> None:
        """Drain the async writer; a surfaced background failure must not
        abort recovery (the event is already emitted at capture time)."""
        try:
            self.ckpt.wait()
        except (TransientFault, OSError):
            pass

    def _save_periodic(self, step: int, state: TrainState) -> None:
        with obs.span("train/checkpoint_async", step=step):
            try:
                self.ckpt.save_async(step, state, extra=self._extra(step))
            except (TransientFault, OSError) as e:
                # either the previous background write failed (surfaced by
                # save_async's wait) or the snapshot itself did — fall back
                # to a synchronous retried save so the rollback target
                # stays fresh
                obs.event("ckpt_save_error", step=step, error=repr(e))
                self._retry(lambda: self.ckpt.save(step, state,
                                                   extra=self._extra(step)),
                            label="ckpt_save")
        obs.event("checkpoint", step=step, mode="async")

    def _rollback(self, like: TrainState, start: int, report: TrainerReport,
                  *, poison: bool) -> Tuple[TrainState, int]:
        """Restore the last good checkpoint (params, opt, iterator position,
        partial EpochLog) and truncate the report to match."""
        with obs.span("train/rollback"):
            self._wait_ckpt()
            state, extra = self._retry(
                lambda: self.ckpt.restore(like, fallback=True),
                label="ckpt_restore")
            # NOTE: the skip list is deliberately NOT restored here — the
            # checkpoint predates the failures just recorded, and merging
            # an older snapshot must never undo in-memory poison status
            ckpt_step, data_state, log, _ = unpack_train_extra(extra)
            if data_state is not None:
                self.data.restore(data_state)
            if log is not None:
                self.epoch_log = log
            done = max(ckpt_step - start, 0)
            del report.losses[done:]
            del report.step_times[done:]
            self.divergence.reset()
        obs.metrics.counter("train_rollbacks_total").inc()
        obs.event("rollback", to_step=ckpt_step, poison_batch=poison)
        return state, ckpt_step

    def _remesh(self, e: PeerLossFault, step: int, start: int,
                state: TrainState, report: TrainerReport
                ) -> Tuple[TrainState, int]:
        """Tier 4: elastic re-mesh after a confirmed peer loss.

        Checkpoint (pinned at the batch about to run), shrink the mesh's
        data axis past the dead hosts, restore + re-shard onto the
        survivors, and resume in-process. The restored iterator position
        and partial EpochLog make the replayed steps re-log identical
        (sl, runtime) records, so SeqPoint selection survives the shrink;
        only the communication stats (dp_wire_bytes) change with the
        smaller DP degree, as they physically must.
        """
        lost = sorted(set(e.hosts) | self.cluster.dead_hosts)
        with obs.span("train/remesh", step=step, lost=lost):
            # pin the survivors' state before touching the mesh: if the
            # shrink itself fails we can still resume from here
            self._wait_ckpt()
            # the pulse runs before the fetch: the iterator still points
            # at the batch of ``step``
            extra = pack_train_extra(step, self.data.state(), self.epoch_log,
                                     self.skiplist)
            self._retry(lambda: self.ckpt.save(step, state, extra=extra),
                        label="ckpt_save")
            obs.event("checkpoint", step=step, mode="remesh")
            # shrink: raises ClusterFailure when nothing survives
            new_mesh, _ = self.cluster.domains.surviving_mesh(lost)
            self.cluster = self.cluster.after_loss(e.hosts)
            self.run = dataclasses.replace(self.run, mesh=new_mesh)
            state, extra = self._retry(
                lambda: self.ckpt.restore(state, fallback=True),
                label="ckpt_restore")
            ckpt_step, data_state, log, skip_state = unpack_train_extra(extra)
            if data_state is not None:
                self.data.restore(data_state)
            if log is not None:
                self.epoch_log = log
            self.skiplist.restore(skip_state)
            state, n_sharded = elastic.reshard_state(state, self.run)
            done = max(ckpt_step - start, 0)
            del report.losses[done:]
            del report.step_times[done:]
            self.divergence.reset()
        report.remeshes += 1
        report.lost_hosts.extend(lost)
        mreg = obs.metrics
        mreg.counter("train_remeshes_total").inc()
        mreg.gauge("cluster_healthy_hosts").set(len(self.cluster.hosts))
        mreg.gauge("train_dp_degree").set(new_mesh.data_degree)
        obs.event("remesh", step=ckpt_step, lost_hosts=lost,
                  new_shape=list(new_mesh.shape),
                  data_degree=new_mesh.data_degree,
                  surviving_hosts=list(self.cluster.hosts),
                  resharded_params=n_sharded)
        return state, ckpt_step

    def _handle_preemption(self, step: int, start: int, state: TrainState,
                           pre_fetch_state: Dict[str, int],
                           report: TrainerReport) -> TrainerReport:
        """Graceful drain on preemption: emergency checkpoint pointing at
        the interrupted batch, then hand back a partial report. A fresh
        Trainer resumes at exactly this batch and the stitched run is
        indistinguishable from an uninterrupted one."""
        report.preempted = True
        report.steps = step - start
        report.epoch_log = self.epoch_log
        obs.metrics.counter("train_preemptions_total").inc()
        if self.ckpt is not None:
            with obs.span("train/checkpoint_preempt", step=step):
                self._wait_ckpt()
                extra = pack_train_extra(step, pre_fetch_state,
                                         self.epoch_log, self.skiplist)
                self._retry(lambda: self.ckpt.save(step, state, extra=extra),
                            label="ckpt_save")
            obs.event("checkpoint", step=step, mode="preempt")
        obs.event("preempted", step=step, completed=step - start,
                  can_resume=self.ckpt is not None)
        return report

    def seqpoints(self, **kw) -> SeqPointSet:
        return select_seqpoints(self.epoch_log, **kw)
