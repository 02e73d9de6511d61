import os
# CPU-only compile tool: virtual host devices, never the accelerator
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Modes (DESIGN.md §6):
  compile  — rolled layer scan, both meshes: proves the sharding config is
             coherent, reports memory_analysis (true peak footprint).
  roofline — single-pod, layer stack compiled UNROLLED at 1x and 2x the
             interleave period; per-period costs extrapolate exactly to full
             depth (lax.scan bodies are otherwise counted once by
             cost_analysis).

Usage:
  python -m repro.launch.dryrun --mode compile --mesh both
  python -m repro.launch.dryrun --mode roofline --arch rwkv6-3b --shape train_4k
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import (
    MULTI_POD,
    SINGLE_POD,
    MeshConfig,
    ModelConfig,
    OptimizerConfig,
    RunConfig,
    ShapeConfig,
    StepKind,
    get_model_config,
    get_shape,
    list_archs,
    shapes_for,
)
from repro.dist.sharding import batch_specs, cache_specs, \
    dp_grad_reduce_elems, param_specs
from repro.launch.mesh import make_mesh
from repro.obs import span
from repro.obs.projection import cell_collective_projection, \
    collective_projection_report
from repro.models.model_zoo import build_model
from repro.models.transformer import Runtime
from repro.perfmodel.hlo import CollectiveStats, parse_collectives
from repro.perfmodel.machine import TPU_V5E, TPU_V5E_HBM_GB
from repro.perfmodel.memory import structural_memory
from repro.perfmodel.traffic import hbm_traffic
from repro.perfmodel.model_flops import model_flops, param_count
from repro.train.train_step import build_train_step, init_train_state, \
    train_state_specs

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results")


def default_run(cfg: ModelConfig, shape: ShapeConfig,
                mesh: MeshConfig, **overrides) -> RunConfig:
    """Production defaults per cell (DESIGN.md §5/§8)."""
    n_total = param_count(cfg, active=False)
    moment_dtype = "bfloat16" if n_total > 100e9 else "float32"
    is_train = shape.step == StepKind.TRAIN
    dp_degree = (mesh.num_devices
                 if overrides.get("parallelism") == "dp_only"
                 else mesh.data_degree)
    # gradient accumulation keeps backward residuals bounded (production
    # practice; the per-microbatch grad all-reduce overlaps the next
    # microbatch's backward under XLA's latency-hiding scheduler). Pick the
    # smallest power of two keeping per-device remat checkpoints <~4 GB.
    nmicro = overrides.pop("microbatches", 0)
    if not nmicro:
        nmicro = 1
        if is_train:
            # remat checkpoints shard over the batch (dp) axis only
            ckpt_bytes = (cfg.num_layers * shape.global_batch
                          * shape.seq_len * cfg.d_model * 2 / dp_degree)
            target = 4 * 2**30
            while (nmicro < shape.global_batch // dp_degree
                   and ckpt_bytes / nmicro > target):
                nmicro *= 2
    kw: Dict[str, Any] = dict(
        model=cfg, shape=shape, mesh=mesh,
        optimizer=OptimizerConfig(moment_dtype=moment_dtype),
        # >100B archs need ZeRO-style storage sharding even at serving
        fsdp=is_train or n_total > 100e9,
        fsdp_over_pods=n_total > 100e9,
        remat="block" if is_train else "none",
        microbatches=nmicro,
    )
    kw.update(overrides)
    return RunConfig(**kw)


def _runtime(run: RunConfig, roofline: bool, n_periods: int) -> Runtime:
    return Runtime(
        tp_degree=run.mesh.model_degree if run.parallelism == "tp" else 1,
        attn_chunk=run.attn_chunk,
        unroll_layers=roofline,
        attn_unroll=64 if roofline else 1,   # >= max chunk count in use
        remat=run.remat,
        param_dtype=jnp.dtype(run.param_dtype),
        compute_dtype=jnp.dtype(run.compute_dtype),
        moe_full_ep=run.moe_full_ep,
    )


def _reduced(cfg: ModelConfig, k: int) -> ModelConfig:
    """k interleave periods of depth (for roofline extrapolation)."""
    if cfg.encoder is not None:
        return cfg.with_overrides(
            num_layers=k,
            encoder=dataclasses.replace(cfg.encoder, num_layers=k))
    return cfg.with_overrides(num_layers=k * cfg.interleave_period)


def _n_periods(cfg: ModelConfig) -> int:
    if cfg.encoder is not None:
        return cfg.num_layers
    return cfg.num_layers // cfg.interleave_period


def _with_sharding(sds_tree, spec_tree, mesh):
    return jax.tree.map(
        lambda sds, spec: jax.ShapeDtypeStruct(
            sds.shape, sds.dtype, sharding=NamedSharding(mesh, spec)),
        sds_tree, spec_tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def lower_cell(cfg: ModelConfig, run: RunConfig, mesh,
               roofline: bool) -> jax.stages.Lowered:
    from repro.dist.axes import set_dp_axes

    set_dp_axes(("pod", "data", "model")
                if run.parallelism == "dp_only" else None)
    model = build_model(cfg, _runtime(run, roofline, _n_periods(cfg)))
    shape = run.shape
    rng = jax.random.PRNGKey(0)

    if shape.step == StepKind.TRAIN:
        state_shape = jax.eval_shape(
            lambda r: init_train_state(model, run, r), rng)
        state_specs = train_state_specs(state_shape, run)
        state_sds = _with_sharding(state_shape, state_specs, mesh)
        batch_shape = model.input_specs(shape)
        bspecs = batch_specs(batch_shape, run.mesh, shape, run.parallelism)
        batch_sds = _with_sharding(batch_shape, bspecs, mesh)
        step = build_train_step(model, run)
        out_shardings = (
            jax.tree.map(lambda s: NamedSharding(mesh, s), state_specs,
                         is_leaf=lambda x: isinstance(x, P)), None)
        with mesh:
            return jax.jit(step, out_shardings=out_shardings,
                           donate_argnums=0).lower(state_sds, batch_sds)

    params_shape = jax.eval_shape(model.init, rng)
    pspecs = param_specs(params_shape, cfg, run.mesh, fsdp=run.fsdp,
                         fsdp_over_pods=run.fsdp_over_pods,
                         moe_full_ep=run.moe_full_ep,
                         parallelism=run.parallelism)
    params_sds = _with_sharding(params_shape, pspecs, mesh)

    if shape.step == StepKind.PREFILL:
        batch_shape = model.input_specs(shape)
        bspecs = batch_specs(batch_shape, run.mesh, shape)
        batch_sds = _with_sharding(batch_shape, bspecs, mesh)
        with mesh:
            return jax.jit(model.prefill).lower(params_sds, batch_sds)

    # decode: one token against a seq_len cache
    b = shape.global_batch
    cache_shape = jax.eval_shape(lambda: model.init_cache(b, shape.seq_len))
    cspecs = cache_specs(cache_shape, cfg, run.mesh, shape)
    cache_sds = _with_sharding(cache_shape, cspecs, mesh)
    tok_specs = model.input_specs(shape)
    tspecs = batch_specs(tok_specs, run.mesh, shape)
    tok_sds = _with_sharding(tok_specs, tspecs, mesh)
    with mesh:
        return jax.jit(model.decode_step, donate_argnums=1).lower(
            params_sds, cache_sds, tok_sds["token"], tok_sds["cache_index"])


def _dp_reduce_elems(cfg: ModelConfig, run: RunConfig) -> Optional[float]:
    """Per-device DP-ring gradient elements for the projection's analytic
    dp term, from the cell's real spec tree (None for non-train steps)."""
    if run.shape.step != StepKind.TRAIN:
        return None
    model = build_model(cfg, _runtime(run, False, _n_periods(cfg)))
    state_shape = jax.eval_shape(
        lambda r: init_train_state(model, run, r), jax.random.PRNGKey(0))
    pspecs = train_state_specs(state_shape, run).params
    return dp_grad_reduce_elems(state_shape.params, pspecs, run.mesh)


def _costs(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis()
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0))}


def run_cell(arch: str, shape_name: str, mesh_cfg: MeshConfig, mesh,
             mode: str, **overrides) -> Dict[str, Any]:
    cfg = get_model_config(arch)
    shape = get_shape(shape_name)
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": "x".join(map(str, mesh_cfg.shape)),
                           "mode": mode, "status": "ok"}
    t0 = time.time()
    try:
        if mode == "compile":
            run = default_run(cfg, shape, mesh_cfg, **overrides)
            with span("dryrun/lower", arch=arch, shape=shape_name):
                lowered = lower_cell(cfg, run, mesh, roofline=False)
            with span("dryrun/compile", arch=arch, shape=shape_name):
                compiled = lowered.compile()
            ma = compiled.memory_analysis()
            rec["memory"] = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
            }
            live = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                    - ma.alias_size_in_bytes)
            rec["memory"]["live_bytes_per_device"] = int(live)
            rec["memory"]["fits_v5e_16g"] = bool(
                live < TPU_V5E_HBM_GB * 2**30)
            # CPU-backend bf16->f32 promotion inflates temps; also record
            # the analytic TPU-side estimate (perfmodel.memory)
            rec["memory"].update(structural_memory(
                run, int(ma.argument_size_in_bytes)))
            rec.update(_costs(compiled))
            coll_stats = parse_collectives(compiled.as_text())
            rec["collectives"] = coll_stats.to_dict()
            # analytic-vs-measured collective bytes (obs.projection): the
            # projection-error report the ROADMAP asks for, per cell. The
            # rolled scans appear once in the HLO text: one interleave
            # period of layer collectives, one microbatch body of grad
            # reduces.
            rec["projection"] = cell_collective_projection(
                cfg, shape, run, coll_stats,
                layers_counted=cfg.interleave_period, micro_counted=1,
                dp_reduce_elems=_dp_reduce_elems(cfg, run))
        elif mode == "roofline":
            n = _n_periods(cfg)
            full_run = default_run(cfg, shape, mesh_cfg, **overrides)
            n_micro = full_run.microbatches
            # Bilinear extrapolation over (layer periods k, microbatches m):
            # cost(k, m) = C0 + Ck*k + Cm*m + Ckm*k*m, solved from four
            # small unrolled compiles (k, m in {1,2}^2). Captures exactly:
            # per-layer-per-microbatch work (compute + ZeRO-3 gathers) in
            # Ckm, token-proportional per-layer work in Ck, per-microbatch
            # overheads in Cm, optimizer/embed/head in C0.
            points = [(1, 1), (2, 1)]
            if n_micro > 1:
                points += [(1, 2), (2, 2)]
            res = {}
            for k, mcount in points:
                rcfg = _reduced(cfg, k)
                run = default_run(rcfg, shape, mesh_cfg,
                                  **dict(overrides, microbatches=mcount))
                run = dataclasses.replace(run, unroll_layers=1)
                lowered = lower_cell(rcfg, run, mesh, roofline=True)
                compiled = lowered.compile()
                res[(k, mcount)] = dict(_costs(compiled))
                res[(k, mcount)]["coll"] = parse_collectives(
                    compiled.as_text())

            def extrap(metric) -> float:
                c11, c21 = metric(res[(1, 1)]), metric(res[(2, 1)])
                if n_micro == 1:
                    return c11 + (n - 1) * (c21 - c11)
                # exact bilinear: per-microbatch constants (Cm) are NOT
                # multiplied by depth
                c12, c22 = metric(res[(1, 2)]), metric(res[(2, 2)])
                ckm = c22 - c21 - c12 + c11
                ck = c21 - c11 - ckm
                cm = c12 - c11 - ckm
                c0 = c11 - ck - cm - ckm
                return c0 + ck * n + cm * n_micro + ckm * n * n_micro

            flops = extrap(lambda r: r["flops"])
            bytes_ = extrap(lambda r: r["bytes"])
            kinds = set()
            for r in res.values():
                kinds |= set(r["coll"].count)
            coll = CollectiveStats()
            for kind in kinds:
                coll.count[kind] = max(int(extrap(
                    lambda r: r["coll"].count.get(kind, 0))), 0)
                coll.buffer_bytes[kind] = max(int(extrap(
                    lambda r: r["coll"].buffer_bytes.get(kind, 0))), 0)
            rec["flops"] = flops
            # memory term from the analytic traffic model — the CPU-module
            # bytes are promotion/fusion-inflated (perfmodel.traffic doc);
            # both are recorded.
            full_run = default_run(cfg, shape, mesh_cfg, **overrides)
            bytes_model = hbm_traffic(full_run)
            rec["bytes_xla_cpu"] = bytes_
            rec["bytes"] = bytes_model
            rec["collectives"] = coll.to_dict()
            rec["wire_bytes"] = coll.wire_bytes
            rec["projection"] = cell_collective_projection(
                cfg, shape, full_run, coll,
                dp_reduce_elems=_dp_reduce_elems(cfg, full_run))
            mf = model_flops(cfg, shape)
            chips = mesh_cfg.num_devices
            t_comp = flops / TPU_V5E.peak_flops
            t_mem = bytes_model / TPU_V5E.hbm_bw
            t_coll = coll.wire_bytes / TPU_V5E.ici_bw
            rec["terms"] = {"compute_s": t_comp, "memory_s": t_mem,
                            "collective_s": t_coll}
            rec["dominant"] = max(rec["terms"], key=rec["terms"].get)
            rec["model_flops_total"] = mf
            rec["model_flops_per_chip"] = mf / chips
            rec["useful_flops_ratio"] = (mf / chips) / max(flops, 1.0)
            bound = max(t_comp, t_mem, t_coll)
            rec["roofline_fraction"] = (mf / chips / TPU_V5E.peak_flops
                                        ) / max(bound, 1e-12)
        rec["seconds"] = round(time.time() - t0, 1)
    except Exception as e:  # noqa: BLE001 — record, keep sweeping
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        rec["seconds"] = round(time.time() - t0, 1)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--mode", default="compile",
                    choices=["compile", "roofline"])
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = list_archs() if args.arch == "all" else args.arch.split(",")
    meshes = {"single": [SINGLE_POD], "multi": [MULTI_POD],
              "both": [SINGLE_POD, MULTI_POD]}[args.mesh]

    out_path = args.out or os.path.join(
        RESULTS_DIR, f"dryrun_{args.mode}_{args.mesh}.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)

    n_fail = 0
    all_recs = []
    with open(out_path, "w") as f:
        for mesh_cfg in meshes:
            mesh = make_mesh(mesh_cfg)
            for arch in archs:
                cfg = get_model_config(arch)
                shapes = (shapes_for(cfg) if args.shape == "all"
                          else [get_shape(s) for s in args.shape.split(",")])
                for shape in shapes:
                    rec = run_cell(arch, shape.name, mesh_cfg, mesh,
                                   args.mode)
                    line = {k: v for k, v in rec.items() if k != "traceback"}
                    print(json.dumps(line), flush=True)
                    f.write(json.dumps(rec) + "\n")
                    f.flush()
                    all_recs.append(rec)
                    if rec["status"] != "ok":
                        n_fail += 1

    # per-cell projection-error report: analytic wire bytes vs measured HLO
    # collective bytes (obs.projection closes the ROADMAP open item here)
    report = collective_projection_report(all_recs)
    proj_path = out_path[:-len(".jsonl")] + "_projection.json" \
        if out_path.endswith(".jsonl") else out_path + ".projection.json"
    with open(proj_path, "w") as f:
        json.dump(report, f, indent=1)
    print("\nprojection error (analytic vs measured collective bytes):",
          file=sys.stderr)
    for c in report["cells"]:
        print(f"  {c['cell']:48s} analytic={c['analytic_wire_bytes']:.3e} "
              f"measured={c['measured_wire_bytes']:.3e} "
              f"rel_error={c['rel_error']:.3f} "
              f"claimed={c.get('rel_error_claimed', c['rel_error']):.3f}",
              file=sys.stderr)
    print(f"  max_rel_error={report['max_rel_error']:.3f} "
          f"claimed={report['max_rel_error_claimed']:.3f} "
          f"({report['num_cells']} cells) -> {proj_path}", file=sys.stderr)
    print(f"\n{'FAILURES: ' + str(n_fail) if n_fail else 'ALL CELLS OK'}",
          file=sys.stderr)
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
