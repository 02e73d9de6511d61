"""One run of one cell: set-up, the measured window, the readers, the check.

The cell names a configuration file and a traffic file; nothing in this
module belongs to one of them. The system under test is the program's own
training entry point, ``repro.train.trainer.Trainer.train``, running the
jitted step of ``repro.train.train_step.build_train_step`` on one device.
The harness hands it the benchmark's weights (through the trainer's resume
hook) and the benchmark's batches (as its data iterator), and nothing else.

Set-up, all counted in ``setup_s``: the epoch's batches are planned from
the seed, the trainer is built, the weights are made on the device in one
jitted call, and one ``train()`` call runs three batches the seed picks
(the steps that are checked) and then one batch at every other padded
length of the epoch, so that every program the window can run is compiled or loaded
from the persistent cache. The window is one more ``train()`` call on the
same trainer and state. It runs from the first batch fetch until the first
fetch at or after ``seconds``; the trainer reads each step's loss before it
fetches again, so every step fetched before that has finished on the
device.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from bench.lib import check
from bench.lib.traffic import make_epoch

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ALIGN = "bench/align"
CHECKED_STEPS = 3


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class WindowClosed(Exception):
    """Raised by the feed at the first fetch after the window's length."""


def load_json(rel: str, root: str = ROOT) -> Any:
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def cell_files(workload: str, root: str = ROOT) -> Tuple[dict, dict, dict, dict]:
    """(BENCHMARK.json, cell, configuration file, traffic file)."""
    bench = load_json("BENCHMARK.json", root)
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfgspec = load_json(entry["file"], root)
    traffic = load_json(os.path.join("bench", "traffic",
                                     cell["traffic"] + ".json"), root)
    return bench, cell, cfgspec, traffic


def load_reader(name: str, root: str = ROOT) -> Callable:
    """``read(window)`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peak_table(kind: str, root: str = ROOT) -> dict:
    """Published peaks of one chip of ``kind``; an unknown kind is an error."""
    table = load_json(os.path.join("bench", "peaks.json"), root)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json; "
                       f"known: {sorted(table)}")
    return table[kind]


# ---------------------------------------------------------------------------
# what the trainer is handed


@dataclass
class Fetch:
    t: float                 # perf_counter at the fetch
    sl: int
    lens: np.ndarray


class Feed:
    """The trainer's data iterator: the given batches of the epoch, in order,
    each fetch time-stamped. With ``seconds`` set, the first fetch at or
    after ``seconds`` past the first raises ``WindowClosed``."""

    def __init__(self, epoch, indices, seconds: Optional[float] = None):
        self.epoch = epoch
        self.indices = indices
        self.seconds = seconds
        self.pos = 0
        self.fetches: List[Fetch] = []
        self.closed_at: Optional[float] = None

    def state(self) -> Dict[str, int]:
        return {"epoch": 0, "batch_index": self.pos, "seed": 0}

    def restore(self, state: Dict[str, int]) -> None:
        self.pos = state["batch_index"]

    def __iter__(self):
        return self

    def __next__(self):
        t = time.perf_counter()
        if self.seconds is not None and self.fetches \
                and t - self.fetches[0].t >= self.seconds:
            self.closed_at = t
            raise WindowClosed()
        b = self.epoch.batch(self.indices(self.pos))
        self.pos += 1
        self.fetches.append(Fetch(t, b.sl, b.lens))
        return b.tokens, b.labels, b.sl


class Capture:
    """Wraps the trainer's jitted step during set-up to keep the newest
    state and to read the checked steps' state. Removed before the window."""

    def __init__(self, step_fn: Callable, on_step: Callable[[int, Any], None]):
        self.step_fn = step_fn
        self.on_step = on_step
        self.calls = 0
        self.state = None

    def __call__(self, state, batch):
        new_state, metrics = self.step_fn(state, batch)
        self.calls += 1
        self.on_step(self.calls, new_state)
        self.state = new_state
        return new_state, metrics


# ---------------------------------------------------------------------------
# what the readers get


@dataclass
class Window:
    """Everything a metric's reader may read about one run.

    Times are ``time.perf_counter`` seconds. With ``--trace 1``,
    ``planes`` holds every line of the profiler's trace for the chips the
    cell uses and for the host, clipped to the window: plane -> line ->
    [(name, start, end), ...]; the device's lines are ``XLA Ops`` (one
    event per operation run) and ``XLA Modules`` (one per program run)."""

    setup_s: float
    window_s: float
    fetches: List[Fetch]
    chips: int
    flops: Callable[[List[int]], float]
    peak: Optional[dict]
    compiles: List[Tuple[str, float]]
    traced: bool = False
    spans: List[Tuple[str, float, float, int]] = field(default_factory=list)
    planes: Dict[str, Dict[str, List[Tuple[str, float, float]]]] = field(
        default_factory=dict)

    @property
    def tokens(self) -> int:
        return int(sum(int(f.lens.sum()) for f in self.fetches))

    @property
    def lo(self) -> float:
        return self.fetches[0].t

    def chip_lines(self, line: str) -> List[List[Tuple[str, float, float]]]:
        """The events of ``line`` on each chip the cell uses, in chip order;
        empty without a trace."""
        from bench.lib.trace import TPU_PLANE

        chips = sorted(p for p in self.planes if p.startswith(TPU_PLANE))
        return [self.planes[p].get(line, []) for p in chips[:self.chips]]

    @property
    def busy_s(self) -> Optional[float]:
        """Seconds in which an operation ran on the device, averaged over
        the chips; None without a trace."""
        from bench.lib.trace import busy

        ops = self.chip_lines("XLA Ops")
        if not ops:
            return None
        hi = self.lo + self.window_s
        return float(np.mean([busy(o, self.lo, hi) for o in ops]))


def _model_config(cfgspec: dict):
    from repro.configs import get_model_config

    fam = check.family(cfgspec)
    base = get_model_config(cfgspec["arch"])
    model = cfgspec["model"]
    fields = {fam.PROGRAM_KEYS[k]: v for k, v in model.items()
              if k in fam.PROGRAM_KEYS}
    for k, f in fam.PROGRAM_KEYS.items():
        if k not in cfgspec["reduced"] and getattr(base, f) != model[k]:
            raise ValueError(
                f"{cfgspec['name']}: {k}={model[k]} but the program's "
                f"{cfgspec['arch']} has {f}={getattr(base, f)} and {k} is "
                "not listed in 'reduced'")
    return base.with_overrides(**fields)


REMAT = ("none", "block", "save_boundaries")    # what the program's Runtime takes


def runtime_fields(cfgspec: dict) -> Dict[str, Any]:
    """The ``RunConfig`` fields that the configuration's optional ``runtime``
    object states: how the train step runs, not what it computes.

    * ``remat``: one of ``REMAT``; ``"block"`` recomputes each layer period
      in the backward pass and keeps only the layer boundaries.

    A key left out keeps the program's default (``"none"``), so a file
    without ``runtime`` builds the program's default ``RunConfig``.
    ``train_mfu`` counts the model's FLOPs without recompute, so
    a cell under ``remat`` reads its MFU against the model's work."""
    rt = cfgspec.get("runtime", {})
    name = cfgspec["name"]
    unknown = sorted(set(rt) - {"remat"})
    if unknown:
        raise ValueError(f"{name}: unknown 'runtime' keys {unknown}; "
                         "known: ['remat']")
    if "remat" in rt and rt["remat"] not in REMAT:
        raise ValueError(f"{name}: runtime.remat={rt['remat']!r}; the "
                         f"program takes one of {list(REMAT)}")
    return dict(rt)


def build_trainer(cfgspec: dict, traffic: dict, feed: Feed):
    """The program's trainer on one device, as the configuration states."""
    from repro.configs import (
        MeshConfig,
        OptimizerConfig,
        RunConfig,
        ShapeConfig,
        StepKind,
    )
    from repro.models import Runtime, build_model
    from repro.train.trainer import Trainer

    mcfg = _model_config(cfgspec)
    o = cfgspec["optimizer"]
    run = RunConfig(
        model=mcfg,
        shape=ShapeConfig("bench", seq_len=traffic["max_len"],
                          global_batch=traffic["batch"], step=StepKind.TRAIN),
        mesh=MeshConfig(shape=(1,), axes=("data",)),
        optimizer=OptimizerConfig(
            name=o["name"], lr=o["lr"], beta1=o["beta1"], beta2=o["beta2"],
            eps=o["eps"], weight_decay=o["weight_decay"],
            grad_clip=o["grad_clip"], warmup_steps=o["warmup_steps"]),
        param_dtype=cfgspec["dtypes"]["param"],
        compute_dtype=cfgspec["dtypes"]["compute"],
        **runtime_fields(cfgspec))
    model = build_model(mcfg, Runtime.from_run(run))
    return Trainer(model, run, feed, total_steps=o["total_steps"])


def _spans_in(tracer, lo: float, hi: float):
    """Program spans (name, start, end, depth) in perf seconds, in [lo, hi]."""
    base = tracer._epoch
    out = []
    for ev in tracer.to_chrome_trace()["traceEvents"]:
        s = base + ev["ts"] * 1e-6
        e = s + ev["dur"] * 1e-6
        if s >= lo and e <= hi:
            out.append((ev["name"], s, e, ev.get("args", {}).get("depth", 0)))
    return out


class Program:
    """The program's trainer, handed the benchmark's weights and batches.

    ``checked_steps`` runs one ``train()`` call over the ``checked``
    batches of the epoch and then the batches at ``extra``, and reads
    what the check compares: the first steps' losses, the first gradient
    as the optimizer received it (from the first moment after one step) and
    the parameters' change after the checked steps. The trainer and its
    newest state are then ready for ``hand_over``, which gives that state to
    the next ``train()`` call with the program's own step."""

    def __init__(self, cfgspec: dict, traffic: dict):
        import jax
        import jax.numpy as jnp

        from bench.lib.weights import leaf_norms, make_init_fn
        from repro.train.train_step import init_train_state

        check.row_blocks(cfgspec, traffic["batch"])     # fail before set-up
        self.trainer = build_trainer(cfgspec, traffic, None)
        model, run = self.trainer.model, self.trainer.run
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        self.init_fn = make_init_fn(shapes, cfgspec["init"],
                                    jnp.dtype(cfgspec["dtypes"]["param"]))
        b1 = cfgspec["optimizer"]["beta1"]

        def make_state(k):
            st = init_train_state(model, run, jax.random.PRNGKey(0))
            return st._replace(params=self.init_fn(k))

        self.make_state = jax.jit(make_state)
        self.moment_norms = jax.jit(
            lambda m: {n: x / (1.0 - b1) for n, x in leaf_norms(m).items()})
        self.change_norms = check.change_norms_fn(self.init_fn)
        self.program_step = self.trainer.step_fn
        self.capture: Optional[Capture] = None
        self.handed = None
        self.trainer.init_or_resume = self._resume

    def _resume(self, rng):
        st, self.handed = self.handed, None
        return st, 0

    def checked_steps(self, epoch, checked, seed: int, extra=()
                      ) -> dict:
        from bench.lib.weights import weight_key

        key = weight_key(seed)
        out: Dict[str, Any] = {}

        def on_step(n: int, st) -> None:
            if n == 1:
                out["grad_norms"] = {k: float(v) for k, v in
                                     self.moment_norms(st.opt.m).items()}
            if n == CHECKED_STEPS:
                out["change_norms"] = {k: float(v) for k, v in
                                       self.change_norms(st.params,
                                                         key).items()}

        idx = list(checked) + list(extra)
        self.capture = Capture(self.program_step, on_step)
        self.trainer.step_fn = self.capture
        self.trainer.data = Feed(epoch, lambda p: idx[p])
        self.handed = self.make_state(key)
        rep = self.trainer.train(len(idx))
        out["losses"] = [float(x) for x in rep.losses[:CHECKED_STEPS]]
        return out

    def hand_over(self) -> None:
        """The newest state goes to the next ``train()``, which runs the
        program's step unwrapped."""
        self.trainer.step_fn = self.program_step
        self.handed, self.capture.state = self.capture.state, None

    def drop(self) -> None:
        self.handed = None
        if self.capture is not None:
            self.capture.state = None


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: Optional[float] = None, root: str = ROOT,
             require_chip: bool = True,
             cfgspec: Optional[dict] = None,
             traffic: Optional[dict] = None,
             log=lambda msg: print(msg, file=sys.stderr, flush=True)
             ) -> Dict[str, Any]:
    """One run of ``workload``; returns the result line's object.

    ``cfgspec`` and ``traffic`` replace the files the cell names, and
    ``require_chip=False`` skips the look for a chip: both serve the tests,
    which drive a whole run on the CPU at a small size."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench, cell, cfg_file, traffic_file = cell_files(workload, root)
    cfgspec = cfgspec or cfg_file
    traffic = traffic or traffic_file
    chips = int(cell["chips"])

    import jax

    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu" or len(devices) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    peak = peak_table(devices[0].device_kind, root) if require_chip else None

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from bench.lib.compiles import CompileLog
    from bench.lib.weights import weight_key
    from repro import obs

    compile_log = CompileLog()
    epoch = make_epoch(traffic, cfgspec["model"]["vocab_size"], seed, root)
    checked = epoch.pick(CHECKED_STEPS)
    seen = {epoch.padded[i] for i in checked}
    warm = []
    for i, sl in enumerate(epoch.padded):
        if sl not in seen:
            seen.add(sl)
            warm.append(i)
    program = Program(cfgspec, traffic)
    prog = program.checked_steps(epoch, checked, seed, warm)
    trainer = program.trainer
    log(f"set-up: {CHECKED_STEPS + len(warm)} steps at {len(seen)} padded "
        f"lengths {sorted(seen)}")

    # -- the window -------------------------------------------------------
    program.hand_over()
    feed = Feed(epoch, lambda p: p, seconds=seconds)
    trainer.data = feed
    tracer = trace_dir = None
    if trace:
        tracer = obs.Tracer(enabled=True)
        prev_tracer = obs.set_tracer(tracer)
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        # no Python tracer: it records every Python call of the host loop
        # that trainer_host_ms measures, and floods the host plane
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(ALIGN):
            anchor_ns = time.perf_counter_ns()
    try:
        trainer.train(10 ** 9)
    except WindowClosed:
        pass
    if trace:
        jax.profiler.stop_trace()
        obs.set_tracer(prev_tracer)
    lo, hi = feed.fetches[0].t, feed.closed_at
    steps = feed.fetches
    setup_s = lo - t_start
    init_fn = program.init_fn
    del trainer, program
    gc.collect()
    mem_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices[:chips])

    fam = check.family(cfgspec)
    win = Window(setup_s=setup_s, window_s=hi - lo, fetches=steps,
                 chips=chips, flops=lambda lens: fam.train_flops(cfgspec["model"], lens),
                 peak=peak, compiles=compile_log.between(lo, hi),
                 traced=bool(trace))
    breakdown = None
    if trace:
        from bench.lib import breakdown as bd
        from bench.lib import trace as tr

        win.spans = _spans_in(tracer, lo, hi)
        profile = tr.load(trace_dir)
        off = anchor_ns - tr.marker_ns(profile, ALIGN)   # trace ns -> perf ns
        win.planes = tr.planes(profile, (tr.TPU_PLANE, tr.HOST_PLANE),
                               lo * 1e9 - off, hi * 1e9 - off,
                               to_time=lambda t: (t + off) * 1e-9)
        del profile
        shutil.rmtree(trace_dir, ignore_errors=True)
        breakdown = bd.read(win)

    kind = "per_layer" if trace else "end_to_end"
    metrics: Dict[str, Any] = {}
    for m in bench[kind]:
        value = load_reader(m["name"], root)(win)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # -- the reference, once the program's state is gone -----------------
    t_ref = time.perf_counter()
    ref = check.reference_readings(cfgspec, init_fn, weight_key(seed),
                                   [epoch.batch(i) for i in checked],
                                   pad_to=traffic["max_len"])
    numbers = check.gaps(prog, ref)
    limits = cfgspec["limits"]
    correct = check.verdict(numbers, limits)
    log(f"reference: {time.perf_counter() - t_ref:.1f} s; losses program "
        f"{prog['losses']} reference {ref['losses']}")

    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": mem_peak}
    if trace:
        dev["busy_s"] = win.busy_s
        dev["window_s"] = win.window_s
    checks = {k: {"value": numbers[k], "limit": limits[k]}
              for k in check.NUMBERS}
    for k, v in checks.items():
        log(f"check {k}: {v['value']!r} limit {v['limit']!r}")
    result = {"correct": bool(correct), "attempted": len(steps),
              "failed": 0, "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result
