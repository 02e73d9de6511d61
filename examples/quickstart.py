"""Quickstart: SeqPoint in two minutes.

Trains a tiny GNMT on synthetic IWSLT-like data for one short epoch while
the trainer logs (SL, runtime) per iteration, then selects SeqPoints and
shows how few iterations reproduce the epoch's total time — the paper's core
claim, end to end.

With observability on (``--obs-dir DIR`` or ``REPRO_OBS_DIR=DIR``), the run
also writes a Perfetto-loadable Chrome trace, a metrics snapshot with
SL-keyed step-time histograms, and a JSONL event log, and checks the
SeqPoint projection live against the measured epoch (repro.obs).

With fault injection armed (``REPRO_FAULTS=<plan>`` or ``--chaos``), the run
finishes with two chaos drills: a single-process one (NaN loss, preemption,
corrupt checkpoint, flaky loader) and a multi-host one (a peer lost mid-run
forces an elastic re-mesh onto the surviving hosts) — both must recover and
produce the same SeqPoint selection as a fault-free reference run
(repro.resilience).

With ``--serve-sched``, the run is a serving-load drill instead: a skewed
SL request stream through the SL-aware continuous-batching scheduler
(repro.serve.sched) vs the run-to-completion baseline, with a live
Prometheus scrape of the serve metrics mid-run; exits non-zero unless the
scheduler cuts padding waste by >= 25% at equal tokens served.

    PYTHONPATH=src python examples/quickstart.py [--obs-dir results/obs]
    REPRO_FAULTS="nan_loss@5,preempt@9,ckpt_corrupt@9" \
        PYTHONPATH=src python examples/quickstart.py
    PYTHONPATH=src python examples/quickstart.py --serve-sched
"""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro import obs
from repro.core import select_seqpoints, frequent, median, worst, prior
from repro.core.characterize import WallclockProvider, epoch_log_from_plan
from repro.core.reproduction import SETUPS
from repro.data.batching import plan_epoch
from repro.launch.compile_cache import enable_compile_cache

# fires data-loader, NaN-loss, straggler, preemption, and silent-checkpoint
# -corruption faults inside a 14-step run checkpointed every 4 steps
DEFAULT_CHAOS_SPEC = ("data_fetch@2,nan_loss@5,straggler@6:delay=0.05,"
                      "preempt@9,ckpt_corrupt@9")

# multi-host drill: a late heartbeat at step 4, then host 1 of 4 dies at
# step 7 — the trainer must confirm the loss, shrink the mesh to 3 hosts,
# and finish with the fault-free SeqPoint selection
ELASTIC_CHAOS_SPEC = "peer_slow@4:host=2:delay=0.02,peer_loss@7:host=1"


def chaos_drill() -> bool:
    """Train under injected faults, recover, and check SeqPoint parity
    against a fault-free reference run. Returns True on parity."""
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
    from repro.resilience import faults
    from repro.train.trainer import Trainer

    spec = os.environ.get("REPRO_FAULTS") or DEFAULT_CHAOS_SPEC
    seed = int(os.environ.get("REPRO_FAULTS_SEED", "0"))
    steps = 14

    def make_trainer(ckpt_dir):
        cfg = smoke_config("starcoder2-3b").with_overrides(
            num_layers=2, d_model=64, d_ff=128, vocab_size=256)
        run = RunConfig(
            model=cfg,
            shape=ShapeConfig("chaos", seq_len=32, global_batch=8,
                              step=StepKind.TRAIN),
            mesh=MeshConfig(shape=(1,), axes=("data",)),
            optimizer=OptimizerConfig(lr=1e-3, warmup_steps=2),
            param_dtype="float32", compute_dtype="float32")
        data = DataIterator(IWSLT_LIKE, samples_per_epoch=256, batch_size=8,
                            vocab_size=cfg.vocab_size, granularity=8, seed=1)
        model = build_model(cfg, Runtime.from_run(run))
        return Trainer(model, run, data, ckpt_dir=ckpt_dir, ckpt_every=4,
                       total_steps=steps + 2)

    obs.event("chaos_drill_start", spec=spec, seed=seed, steps=steps)
    print(f"\nchaos drill: {steps} steps under REPRO_FAULTS={spec!r}")
    faults.install(None)                      # fault-free reference first
    with tempfile.TemporaryDirectory() as d:
        ref_tr = make_trainer(os.path.join(d, "ck"))
        ref_rep = ref_tr.train(steps)
        ref_sp = ref_tr.seqpoints(error_threshold=0.1, n_threshold=32)

    faults.install(faults.FaultPlan.parse(spec, seed=seed))
    with tempfile.TemporaryDirectory() as d:
        ck = os.path.join(d, "ck")
        tr = make_trainer(ck)
        rep = tr.train(steps)
        losses = list(rep.losses)
        pos = (rep.resumed_from or 0) + rep.steps
        restarts = 0
        while rep.preempted or pos < steps:   # resume until the run is done
            restarts += 1
            tr = make_trainer(ck)
            rep = tr.train(steps - pos)
            start = rep.resumed_from or 0
            losses = losses[:start] + list(rep.losses)
            pos = start + rep.steps
        sp = tr.seqpoints(error_threshold=0.1, n_threshold=32)
    faults.install(None)

    parity = (sp.seq_lens == ref_sp.seq_lens
              and np.allclose(sp.weights, ref_sp.weights)
              and np.allclose(losses, ref_rep.losses, rtol=1e-5, atol=1e-6))
    print(f"  recovered: {restarts} restart(s), {rep.rollbacks} rollback(s) "
          f"in final segment, epoch log {tr.epoch_log.num_iterations} "
          f"iterations")
    print(f"  seqpoint parity vs fault-free run: "
          f"{'OK' if parity else 'MISMATCH'} "
          f"(SLs {sp.seq_lens} == {ref_sp.seq_lens})")
    obs.event("chaos_drill_end", ok=bool(parity), restarts=restarts,
              seqpoint_sls=sp.seq_lens)
    return parity


def elastic_drill() -> bool:
    """Lose a host mid-run on a 4-way DP mesh, re-mesh over the survivors,
    and check SeqPoint parity against a fault-free reference. Returns True
    on parity."""
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
    from repro.resilience import faults
    from repro.train.trainer import Trainer

    spec = ELASTIC_CHAOS_SPEC
    seed = int(os.environ.get("REPRO_FAULTS_SEED", "0"))
    steps = 12

    def make_trainer(ckpt_dir):
        cfg = smoke_config("starcoder2-3b").with_overrides(
            num_layers=2, d_model=64, d_ff=128, vocab_size=256)
        run = RunConfig(
            model=cfg,
            shape=ShapeConfig("elastic", seq_len=32, global_batch=8,
                              step=StepKind.TRAIN),
            mesh=MeshConfig(shape=(4,), axes=("data",)),
            optimizer=OptimizerConfig(lr=1e-3, warmup_steps=2),
            param_dtype="float32", compute_dtype="float32")
        data = DataIterator(IWSLT_LIKE, samples_per_epoch=256, batch_size=8,
                            vocab_size=cfg.vocab_size, granularity=8, seed=1)
        model = build_model(cfg, Runtime.from_run(run))
        return Trainer(model, run, data, ckpt_dir=ckpt_dir, ckpt_every=4,
                       total_steps=steps + 2)

    obs.event("elastic_drill_start", spec=spec, seed=seed, steps=steps)
    print(f"\nelastic drill: {steps} steps on a 4-host DP mesh under "
          f"{spec!r}")
    faults.install(None)                      # fault-free reference first
    with tempfile.TemporaryDirectory() as d:
        ref_tr = make_trainer(os.path.join(d, "ck"))
        ref_rep = ref_tr.train(steps)
        ref_sp = ref_tr.seqpoints(error_threshold=0.1, n_threshold=32)

    faults.install(faults.FaultPlan.parse(spec, seed=seed))
    with tempfile.TemporaryDirectory() as d:
        tr = make_trainer(os.path.join(d, "ck"))
        rep = tr.train(steps)                 # re-mesh happens in-call
        sp = tr.seqpoints(error_threshold=0.1, n_threshold=32)
    faults.install(None)

    # parity is on losses and the SeqPoint selection — (SL, runtime) records
    # are what selection reads; dp_wire_bytes legitimately shrinks with DP
    parity = (rep.remeshes == 1
              and tr.run.mesh.shape == (3,)
              and sp.seq_lens == ref_sp.seq_lens
              and np.allclose(sp.weights, ref_sp.weights)
              and np.allclose(rep.losses, ref_rep.losses,
                              rtol=1e-5, atol=1e-6))
    print(f"  lost host(s) {rep.lost_hosts}: {rep.remeshes} re-mesh(es), "
          f"mesh {(4,)} -> {tr.run.mesh.shape}, epoch log "
          f"{tr.epoch_log.num_iterations} iterations")
    print(f"  seqpoint parity vs fault-free run: "
          f"{'OK' if parity else 'MISMATCH'} "
          f"(SLs {sp.seq_lens} == {ref_sp.seq_lens})")
    obs.event("elastic_drill_end", ok=bool(parity), remeshes=rep.remeshes,
              lost_hosts=list(rep.lost_hosts), seqpoint_sls=sp.seq_lens)
    return parity


def serve_drill() -> bool:
    """Skewed-SL serving-load smoke: the SL-aware continuous-batching
    scheduler (``repro.serve.sched``) vs the run-to-completion baseline on
    the same request stream, with a live Prometheus scrape mid-run.
    Returns True when the scheduler serves the same tokens with >= 25%
    lower padding waste and higher grid throughput."""
    import urllib.request

    import jax

    from repro.configs import (
        MeshConfig,
        OptimizerConfig,
        RunConfig,
        ShapeConfig,
        StepKind,
        smoke_config,
    )
    from repro.models import Runtime, build_model
    from repro.serve import Request, ServeEngine
    from repro.serve.sched import BucketAffinePolicy, run_to_completion

    def make_engine():
        cfg = smoke_config("starcoder2-3b").with_overrides(
            num_layers=2, d_model=64, d_ff=128, vocab_size=256)
        run = RunConfig(
            model=cfg,
            shape=ShapeConfig("serve", seq_len=32, global_batch=8,
                              step=StepKind.TRAIN),
            mesh=MeshConfig(shape=(1,), axes=("data",)),
            optimizer=OptimizerConfig(lr=1e-3, warmup_steps=2),
            param_dtype="float32", compute_dtype="float32")
        model = build_model(cfg, Runtime.from_run(run))
        params = model.init(jax.random.PRNGKey(0))
        return ServeEngine(model, params, batch_size=4, max_len=160,
                           sl_granularity=8)

    def requests(n=24, seed=0):
        # skewed SL mix: mostly short prompts, a wide straggler every 4th
        # arrival — the FIFO-batching worst case (each chunk pads to it)
        rng = np.random.RandomState(seed)
        out = []
        for i in range(n):
            sl = 128 if i % 4 == 0 else int(rng.randint(5, 17))
            out.append(Request(
                prompt=rng.randint(1, 255, size=sl).astype(np.int32),
                max_new_tokens=int(rng.randint(2, 6))))
        return out

    n = 24
    print(f"\nserving-load drill: {n} requests, skewed SLs "
          f"(1-in-4 at 128, rest in [5, 16])")
    obs.event("serve_drill_start", n_requests=n)
    srv = obs.serve_http()

    base = run_to_completion(make_engine(), requests(n))
    sched = make_engine().serve(requests(n), policy=BucketAffinePolicy())

    scrape = urllib.request.urlopen(srv.url, timeout=5).read().decode()
    n_series = sum(1 for ln in scrape.splitlines()
                   if ln.startswith("serve_sched"))
    srv.close()

    for name, s in (("run-to-completion", base), ("sched", sched)):
        print(f"  {name:18s} waste={s.padding_waste:.3f} "
              f"grid_tput={s.grid_throughput:.4f} tokens={s.tokens_out} "
              f"prefills={s.prefills} decode_steps={s.decode_steps}")
    red = 1.0 - sched.padding_waste / base.padding_waste \
        if base.padding_waste else 0.0
    print(f"  padding-waste reduction: {100 * red:.1f}% "
          f"(acceptance bar: 25%)")
    print(f"  live scrape {srv.url}: {n_series} serve_sched series")

    ok = (sched.tokens_out == base.tokens_out
          and sched.padding_waste <= 0.75 * base.padding_waste
          and sched.grid_throughput > base.grid_throughput
          and n_series > 0)
    obs.event("serve_drill_end", ok=bool(ok), waste_base=base.padding_waste,
              waste_sched=sched.padding_waste, reduction=red,
              tokens=sched.tokens_out, scrape_series=n_series)
    print(f"  serving drill: {'OK' if ok else 'FAILED'}")
    return ok


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--obs-dir", default=os.environ.get("REPRO_OBS_DIR"),
                    help="enable tracing/metrics/events, export here")
    ap.add_argument("--chaos", action="store_true",
                    default=bool(os.environ.get("REPRO_FAULTS")),
                    help="run the fault-injection recovery drill "
                         "(auto-on when REPRO_FAULTS is set)")
    ap.add_argument("--serve-sched", action="store_true",
                    help="run only the serving-load drill: SL-aware "
                         "continuous batching vs run-to-completion")
    args = ap.parse_args()
    enable_compile_cache()
    if args.obs_dir:
        obs.enable(out_dir=args.obs_dir)

    if args.serve_sched:
        ok = serve_drill()
        obs.event("run_end", example="quickstart", ok=bool(ok))
        if args.obs_dir:
            paths = obs.export_all()
            print("\nobservability artifacts:")
            for kind, path in sorted(paths.items()):
                print(f"  {kind:13s} {path}")
        sys.exit(0 if ok else 1)

    setup = SETUPS["gnmt"]()
    rng = np.random.RandomState(0)
    sls = setup["dist"].sample(rng, 1280)
    plan = plan_epoch(sls, setup["batch_size"],
                      granularity=setup["granularity"])
    print(f"epoch: {plan.num_batches} iterations, "
          f"{len(set(map(int, plan.padded_sls)))} unique padded SLs")
    obs.event("run_start", example="quickstart", network="gnmt",
              iterations=plan.num_batches)

    print("profiling every unique SL (the expensive ground-truth pass)...")
    provider = WallclockProvider(setup["step_builder"], repeats=3)
    with obs.span("quickstart/profile_epoch"):
        log = epoch_log_from_plan(plan, provider)
    print(f"measured epoch time: {log.total_runtime:.2f}s")

    with obs.span("quickstart/select_seqpoints"):
        sp = select_seqpoints(log, error_threshold=0.02)
    print(f"\nSeqPoints: {sp.num_points} iterations (k={sp.k}) "
          f"-> projected {sp.predicted:.2f}s, error {100*sp.error:.2f}%")
    print(f"  SLs: {sp.seq_lens}")
    obs.event("seqpoints_selected", num_points=sp.num_points, k=sp.k,
              error=sp.error, converged=sp.meta.get("converged"))
    for name, fn in (("frequent", frequent), ("median", median),
                     ("worst", worst), ("prior", prior)):
        b = fn(log)
        print(f"  {name:9s}: {b.num_points:3d} iterations, "
              f"error {100*b.error:6.2f}%")
    red = plan.num_batches / sp.num_points
    print(f"\nprofiling reduction: {red:.0f}x fewer iterations "
          f"(paper reports 214x/345x at full dataset scale)")

    # live projection-error check: price every logged iteration by its
    # nearest SeqPoint and compare against the measured epoch total
    monitor = obs.ProjectionMonitor(sp)
    monitor.observe_log(log)
    rep = monitor.report()
    print(f"projection monitor: projected {rep.projected_total:.2f}s vs "
          f"measured {rep.measured_total:.2f}s "
          f"(rel error {100*rep.rel_error:.2f}%, "
          f"{len(rep.per_sl)} SLs tracked)")
    obs.event("projection_report", projected=rep.projected_total,
              measured=rep.measured_total, rel_error=rep.rel_error)

    if args.chaos:
        ok = chaos_drill()
        ok = elastic_drill() and ok
        if not ok:
            obs.event("run_end", example="quickstart", ok=False)
            if args.obs_dir:
                obs.export_all()
            sys.exit(1)

    obs.event("run_end", example="quickstart")
    if args.obs_dir:
        paths = obs.export_all()
        print("\nobservability artifacts:")
        for kind, path in sorted(paths.items()):
            print(f"  {kind:13s} {path}")


if __name__ == "__main__":
    main()
