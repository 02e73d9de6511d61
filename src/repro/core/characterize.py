"""Characterizer: SeqPoint-driven epoch characterization (DESIGN.md §2).

Two profiling backends feed the same selection/projection machinery:

* ``WallclockProvider`` — really executes jitted steps per unique SL on this
  host (the paper's native-hardware profiling). Per-SL XLA compilation is the
  'autotune' analog: excluded from iteration cost, *measured* as profiling
  cost — it is exactly what SeqPoint amortizes (paper §IV-C2 / §VI-F).
* ``CompiledCostProvider`` — ``jit(...).lower().compile().cost_analysis()``
  per SL; an analytic machine model (TPU v5e + paper-analog configs #2-#5)
  turns FLOPs/bytes into per-iteration seconds. This scales the paper's
  hardware-config sensitivity study (Table II) to machines we don't have.

The reproduction experiments (benchmarks/) select SeqPoints ONCE on config#1
and re-profile only those SLs on other configs — the paper's
architecture-independence claim, measured end to end.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from repro import obs
from repro.core.profile import EpochLog, SLTable
from repro.core.seqpoint import SeqPointSet, select_seqpoints
from repro.data.batching import BatchPlan
from repro.perfmodel.hlo import parse_collectives
from repro.perfmodel.machine import MachineConfig


@dataclass
class ProfileResult:
    runtime: float                       # per-iteration seconds
    stats: Dict[str, float] = field(default_factory=dict)
    profile_cost: float = 0.0            # seconds spent profiling this SL


class WallclockProvider:
    """Measure real per-iteration wallclock for a (model, batch) at a given
    padded SL. ``step_builder(sl) -> (fn, args)`` returns a jittable step and
    its inputs."""

    def __init__(self, step_builder: Callable[[int], Tuple[Callable, tuple]],
                 repeats: int = 3):
        self.step_builder = step_builder
        self.repeats = repeats
        self.cache: Dict[int, ProfileResult] = {}

    def profile(self, sl: int) -> ProfileResult:
        if sl in self.cache:
            obs.metrics.counter("profile_cache_hits_total",
                                provider="wallclock").inc()
            return self.cache[sl]
        import jax
        with obs.span("profile/wallclock", sl=sl):
            t0 = time.perf_counter()
            with obs.span("profile/compile_warmup", sl=sl):
                fn, args = self.step_builder(sl)
                jfn = jax.jit(fn)
                out = jfn(*args)
                jax.block_until_ready(out)            # compile + warmup
            compile_cost = time.perf_counter() - t0
            times = []
            for _ in range(self.repeats):
                t0 = time.perf_counter()
                with obs.span("profile/measure", sl=sl):
                    jax.block_until_ready(jfn(*args))
                times.append(time.perf_counter() - t0)
        res = ProfileResult(runtime=float(np.median(times)),
                            stats={"runtime_std": float(np.std(times))},
                            profile_cost=compile_cost + sum(times))
        mreg = obs.metrics
        mreg.histogram("profile_step_time_s", sl=sl).observe(res.runtime)
        mreg.histogram("profile_cost_s", provider="wallclock",
                       sl=sl).observe(res.profile_cost)
        self.cache[sl] = res
        return res


class CompiledCostProvider:
    """Per-SL compiled cost analysis -> machine-model seconds."""

    def __init__(self, lower_builder: Callable[[int], "jax.stages.Lowered"],
                 machine: MachineConfig, overlap: bool = True):
        self.lower_builder = lower_builder
        self.machine = machine
        self.overlap = overlap
        self.cost_cache: Dict[int, Tuple[float, float, float]] = {}
        self.profile_costs: Dict[int, float] = {}

    def costs(self, sl: int) -> Tuple[float, float, float]:
        if sl not in self.cost_cache:
            t0 = time.perf_counter()
            with obs.span("profile/compiled_cost", sl=sl):
                compiled = self.lower_builder(sl).compile()
                ca = compiled.cost_analysis()
            flops = float(ca.get("flops", 0.0))
            bts = float(ca.get("bytes accessed", 0.0))
            coll = parse_collectives(compiled.as_text()).wire_bytes
            self.cost_cache[sl] = (flops, bts, coll)
            self.profile_costs[sl] = time.perf_counter() - t0
            obs.metrics.histogram("profile_cost_s", provider="compiled",
                                  sl=sl).observe(self.profile_costs[sl])
        else:
            obs.metrics.counter("profile_cache_hits_total",
                                provider="compiled").inc()
        return self.cost_cache[sl]

    def profile(self, sl: int,
                machine: Optional[MachineConfig] = None) -> ProfileResult:
        flops, bts, coll = self.costs(sl)
        m = machine or self.machine
        t = (m.step_time(flops, bts, coll) if self.overlap
             else m.step_time_sum(flops, bts, coll))
        return ProfileResult(runtime=t,
                             stats={"flops": flops, "bytes": bts,
                                    "coll_bytes": coll},
                             profile_cost=self.profile_costs.get(sl, 0.0))


# ---------------------------------------------------------------------------


def epoch_log_from_plan(plan: BatchPlan, provider,
                        machine: Optional[MachineConfig] = None) -> EpochLog:
    """Profile every unique SL in the plan, build the full epoch log (the
    paper's step (1): this is the expensive ground-truth pass)."""
    log = EpochLog(meta={"batch_size": plan.batch_size})
    uniq = sorted(set(int(s) for s in plan.padded_sls))
    results = {}
    for sl in uniq:
        results[sl] = (provider.profile(sl, machine)
                       if machine is not None else provider.profile(sl))
    for sl in plan.padded_sls:
        r = results[int(sl)]
        log.append(int(sl), r.runtime, **r.stats)
    return log


def project_on_config(points: SeqPointSet, provider,
                      machine: Optional[MachineConfig] = None,
                      kind: str = "total") -> float:
    """Profile ONLY the SeqPoint SLs on a (new) config and project (Eq. 1)."""
    def stat(sl: int) -> float:
        r = (provider.profile(sl, machine) if machine is not None
             else provider.profile(sl))
        return r.runtime
    return (points.project_total(stat) if kind == "total"
            else points.project_mean(stat))


def profiling_cost(provider, sls: List[int]) -> float:
    """Seconds spent profiling the given SLs (compile + measure)."""
    total = 0.0
    for sl in sls:
        if hasattr(provider, "cache") and sl in provider.cache:
            total += provider.cache[sl].profile_cost
        elif hasattr(provider, "profile_costs"):
            total += provider.profile_costs.get(sl, 0.0)
    return total
