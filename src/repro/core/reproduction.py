"""End-to-end SeqPoint reproduction on the paper's networks (GNMT, DS2).

Two tracks (DESIGN.md §2/§3):

* Track W (wallclock): really run reduced-size GNMT/DS2 training iterations
  per unique padded SL on this host; SeqPoint + all baselines project the
  epoch's total training time (paper Figs. 11/12, config #1).
* Track A (analytic machine configs): per-SL compiled FLOPs/bytes drive the
  five paper-analog hardware configs (Table II); SeqPoints selected on
  config #1 project times and speedups on configs #2-#5 (Figs. 11-16).

Also measured: per-SL profiling cost (XLA compile+measure seconds) — the
quantity SeqPoint reduces by ~two orders of magnitude (paper §VI-F).

Results cache to results/repro_<network><tag>_<device>.json, keyed by the
platform and device kind that produced them, so a result is only ever
returned on the kind of device it was measured on; benchmarks/ are thin
readers.
"""
from __future__ import annotations

import json
import os
import re
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.core.baselines import ALL_BASELINES
from repro.core.characterize import (
    CompiledCostProvider,
    WallclockProvider,
    epoch_log_from_plan,
    profiling_cost,
    project_on_config,
)
from repro.core.clustering import kmeans_seqpoints
from repro.core.profile import EpochLog
from repro.core.seqpoint import SeqPointSet, select_seqpoints
from repro.data.batching import plan_epoch
from repro.data.synthetic import IWSLT_LIKE, LIBRISPEECH_LIKE
from repro.perfmodel.machine import PAPER_CONFIGS

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results")


# ---------------------------------------------------------------------------
# network setups


def _gnmt_setup():
    import jax
    from repro.models.rnn import GNMT, GNMTConfig

    cfg = GNMTConfig(vocab_size=2048, d_model=96, num_enc_uni=2, num_dec=2)
    model = GNMT(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def step_builder(sl: int):
        batch = model.make_batch(sl, 16, sl, sl)

        def step(p, b):
            loss, _ = model.loss(p, b)
            grads = jax.grad(lambda pp: model.loss(pp, b)[0])(p)
            return loss, jax.tree.map(lambda x, g: x - 1e-4 * g, p, grads)

        return step, (params, batch)

    return dict(step_builder=step_builder, dist=IWSLT_LIKE, batch_size=64,
                granularity=4, sort_first=False, samples=6400)


def _ds2_setup():
    import jax
    from repro.models.rnn import DS2, DS2Config

    cfg = DS2Config(num_freq=64, conv_channels=8, d_h=64, num_gru=2)
    model = DS2(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def step_builder(sl: int):
        batch = model.make_batch(sl, 8, sl)

        def step(p, b):
            loss, _ = model.loss(p, b)
            grads = jax.grad(lambda pp: model.loss(pp, b)[0])(p)
            return loss, jax.tree.map(lambda x, g: x - 1e-4 * g, p, grads)

        return step, (params, batch)

    # DS2 sorts inputs in the first epoch (paper §VI-D artifact)
    return dict(step_builder=step_builder, dist=LIBRISPEECH_LIKE,
                batch_size=32, granularity=64, sort_first=True, samples=3200)


SETUPS: Dict[str, Callable[[], dict]] = {"gnmt": _gnmt_setup,
                                         "ds2": _ds2_setup}


# ---------------------------------------------------------------------------


def _select_all(log: EpochLog, error_threshold: float
                ) -> Dict[str, SeqPointSet]:
    out = {"seqpoint": select_seqpoints(log,
                                        error_threshold=error_threshold)}
    for name, fn in ALL_BASELINES.items():
        out[name] = fn(log)
    out["kmeans"] = kmeans_seqpoints(log, k=out["seqpoint"].num_points)
    return out


def _hlo_op_histogram(lowered) -> Dict[str, int]:
    """Kernel-distribution analog: compiled HLO ops keyed by (op, shape) —
    the shape carries the SL dependence the paper's Fig. 5/8 sees in CUDA
    kernel selection (op *types* alone are SL-invariant under lax.scan)."""
    txt = lowered.compile().as_text()
    ops = re.findall(
        r"= ([a-z][a-z0-9]*\[[0-9,]*\])[^ ]* ([a-z][a-z0-9-]*)\(", txt)
    from collections import Counter
    return dict(Counter(f"{op}:{shape}" for shape, op in ops))


def _device() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def cache_path(network: str, tag: str = "") -> str:
    """Where this device's reproduction result for ``network`` is cached."""
    dev = _device()
    key = re.sub(r"[^a-z0-9]+", "-",
                 f"{dev['platform']}-{dev['kind']}".lower()).strip("-")
    return os.path.join(RESULTS_DIR, f"repro_{network}{tag}_{key}.json")


def run_reproduction(network: str, *, error_threshold: float = 0.02,
                     seed: int = 0, force: bool = False,
                     samples: Optional[int] = None,
                     tag: str = "") -> dict:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    out_path = cache_path(network, tag)
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)

    import jax
    setup = SETUPS[network]()
    if samples:
        setup["samples"] = samples
    rng = np.random.RandomState(seed)
    sls = setup["dist"].sample(rng, setup["samples"])
    plan = plan_epoch(sls, setup["batch_size"],
                      granularity=setup["granularity"],
                      sort_first=setup["sort_first"], seed=seed)
    uniq = sorted(set(int(s) for s in plan.padded_sls))
    result: dict = {
        "network": network,
        "device": _device(),
        "num_iterations": plan.num_batches,
        "num_unique_sls": len(uniq),
        "unique_sls": uniq,
        "sl_histogram": {int(s): int((plan.padded_sls == s).sum())
                         for s in uniq},
        "padding_waste": plan.padding_waste(),
    }

    # ---- Track W: wallclock ------------------------------------------------
    wall = WallclockProvider(setup["step_builder"], repeats=3)
    t0 = time.perf_counter()
    log_w = epoch_log_from_plan(plan, wall)
    full_profile_seconds = time.perf_counter() - t0
    sel_w = _select_all(log_w, error_threshold)
    result["wallclock"] = {
        "total_epoch_seconds": log_w.total_runtime,
        "runtime_by_sl": {int(s): wall.cache[s].runtime for s in uniq},
        "methods": {
            name: {"num_points": s.num_points, "k": s.k,
                   "predicted": s.predicted, "actual": s.actual,
                   "error_pct": 100 * s.error,
                   "seq_lens": s.seq_lens}
            for name, s in sel_w.items()},
        "profiling": {
            "full_seconds": full_profile_seconds,
            "seqpoint_seconds": profiling_cost(
                wall, sel_w["seqpoint"].seq_lens),
            "iterations_full": plan.num_batches,
            "iterations_seqpoint": sel_w["seqpoint"].num_points,
            "iter_reduction": plan.num_batches
            / max(sel_w["seqpoint"].num_points, 1),
        },
    }

    # ---- Track A: five machine configs ------------------------------------
    def lower_builder(sl: int):
        fn, args = setup["step_builder"](sl)
        return jax.jit(fn).lower(*args)

    # no-overlap (sum) execution model: per-SL arithmetic intensity then
    # shapes each hardware config's speedup, as on the paper's real GPU
    # (with the max/roofline model every SL is compute-bound and the
    # sensitivity study degenerates)
    prov = CompiledCostProvider(lower_builder, PAPER_CONFIGS["config1"],
                                overlap=False)
    logs = {c: epoch_log_from_plan(plan, prov, machine=m)
            for c, m in PAPER_CONFIGS.items()}
    sel_a = _select_all(logs["config1"], error_threshold)
    actual = {c: logs[c].total_runtime for c in PAPER_CONFIGS}
    track_a = {"actual_seconds": actual, "methods": {}}
    for name, points in sel_a.items():
        per_cfg = {}
        for c, m in PAPER_CONFIGS.items():
            pred = project_on_config(points, prov, machine=m)
            err = abs(pred - actual[c]) / actual[c] * 100
            # speedup (throughput uplift vs config1), paper Figs. 15/16
            pred1 = project_on_config(points, prov,
                                      machine=PAPER_CONFIGS["config1"])
            sp_actual = actual["config1"] / actual[c]
            sp_pred = pred1 / pred
            per_cfg[c] = {"time_error_pct": err,
                          "speedup_actual": sp_actual,
                          "speedup_pred": sp_pred,
                          "speedup_error_pp": 100 * abs(sp_pred - sp_actual)
                          / sp_actual}
        geo = float(np.exp(np.mean([np.log(max(v["time_error_pct"], 1e-3))
                                    for v in per_cfg.values()])))
        track_a["methods"][name] = {"per_config": per_cfg,
                                    "geomean_time_error_pct": geo,
                                    "num_points": points.num_points}
    # per-SL sensitivity (Figs. 13/14): speedup of each SL, config1 -> c
    sens = {}
    for c, m in PAPER_CONFIGS.items():
        if c == "config1":
            continue
        sens[c] = {int(sl): prov.profile(sl, PAPER_CONFIGS["config1"]).runtime
                   / prov.profile(sl, m).runtime for sl in uniq}
    track_a["per_sl_speedup"] = sens
    track_a["per_sl_stats"] = {
        int(sl): dict(prov.profile(sl).stats) for sl in uniq}
    result["analytic"] = track_a

    # ---- Fig. 8 analog: HLO op histograms for nearby/far SLs ---------------
    if len(uniq) >= 4:
        picks = [uniq[0], uniq[1], uniq[len(uniq) // 2], uniq[-1]]
        hist = {int(sl): _hlo_op_histogram(lower_builder(sl)) for sl in picks}
        result["op_histograms"] = hist

    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    return result
