"""SeqPoint-accelerated characterization of an assigned architecture.

For a production (arch, mesh, batch) and a document-length distribution,
answering "what does a full variable-SL training epoch cost?" requires
compiling every unique padded SL — minutes of XLA time per SL at fleet
scale. This driver (1) selects SeqPoints from a *cheap analytic* runtime
proxy, (2) compiles ONLY the SeqPoint SLs on the production mesh, and
(3) projects epoch totals (time / FLOPs / HBM / collective bytes),
reporting the measured compile-time saving — SeqPoint's §VI-F claim
restated for the XLA era (DESIGN.md §2).

    PYTHONPATH=src python examples/characterize_arch.py \
        --arch qwen2-moe-a2.7b --samples 2048
"""
import os

# CPU-only compile tool: virtual host devices, never the accelerator
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-moe-a2.7b")
    ap.add_argument("--samples", type=int, default=2048)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--max-sl", type=int, default=4096)
    ap.add_argument("--granularity", type=int, default=256)
    args = ap.parse_args()

    import dataclasses

    import jax

    from repro.configs import SINGLE_POD, ShapeConfig, StepKind, \
        get_model_config
    from repro.core import EpochLog, select_seqpoints
    from repro.data.batching import plan_epoch
    from repro.data.synthetic import lm_documents
    from repro.launch.dryrun import default_run, lower_cell, _reduced
    from repro.launch.mesh import make_mesh
    from repro.perfmodel.hlo import parse_collectives
    from repro.perfmodel.machine import TPU_V5E
    from repro.perfmodel.model_flops import model_flops, param_count

    cfg = get_model_config(args.arch)
    rng = np.random.RandomState(0)
    dist = lm_documents(args.max_sl)
    sls = dist.sample(rng, args.samples)
    plan = plan_epoch(sls, args.batch, granularity=args.granularity)
    uniq = sorted(set(int(s) for s in plan.padded_sls))
    print(f"{args.arch}: epoch of {plan.num_batches} iterations, "
          f"{len(uniq)} unique padded SLs {uniq[:5]}...{uniq[-3:]}")

    # (1) cheap analytic proxy for selection (no compiles)
    n_active = param_count(cfg, active=True)
    log = EpochLog()
    for sl in plan.padded_sls:
        t = 6 * n_active * args.batch * int(sl) / SINGLE_POD.num_devices \
            / TPU_V5E.peak_flops
        log.append(int(sl), t)
    sp = select_seqpoints(log, error_threshold=0.02)
    print(f"SeqPoints: {sp.num_points} of {len(uniq)} unique SLs "
          f"-> compile {sp.num_points} instead of {len(uniq)} shapes")

    # (2) compile only the SeqPoint SLs on the production mesh
    mesh = make_mesh(SINGLE_POD)
    per_sl = {}
    t0 = time.perf_counter()
    for sl in sp.seq_lens:
        shape = ShapeConfig(f"sl{sl}", seq_len=int(sl),
                            global_batch=args.batch, step=StepKind.TRAIN)
        rcfg = _reduced(cfg, 1)
        run = dataclasses.replace(
            default_run(rcfg, shape, SINGLE_POD), unroll_layers=1)
        compiled = lower_cell(rcfg, run, mesh, roofline=True).compile()
        ca = compiled.cost_analysis()
        n_periods = cfg.num_layers // cfg.interleave_period
        flops = float(ca.get("flops", 0.0)) * n_periods   # 1-period scaled
        coll = parse_collectives(compiled.as_text()).wire_bytes * n_periods
        per_sl[int(sl)] = {"flops": flops, "coll": coll,
                           "t": max(flops / TPU_V5E.peak_flops,
                                    coll / TPU_V5E.ici_bw)}
    compile_seconds = time.perf_counter() - t0

    # (3) project the epoch
    total_t = sp.project_total(lambda s: per_sl[int(s)]["t"])
    total_f = sp.project_total(lambda s: per_sl[int(s)]["flops"])
    est_full = compile_seconds / sp.num_points * len(uniq)
    print(f"projected epoch: {total_t:.1f}s/epoch roofline-bound, "
          f"{total_f:.3g} per-chip FLOPs")
    print(f"profiling cost: {compile_seconds:.0f}s for "
          f"{sp.num_points} compiles vs ~{est_full:.0f}s for all "
          f"{len(uniq)} unique SLs ({est_full/max(compile_seconds,1e-9):.1f}x"
          f" saved)")


if __name__ == "__main__":
    main()
