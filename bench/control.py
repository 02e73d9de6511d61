"""Readings that the limits of ``correct`` are set from, at a cell's size.

    python3 bench/control.py --workload <name> --seeds 1,2,... \
        --control-seeds 1,2,3

For each of ``--seeds`` the program's checked steps are read against the
reference (the lower readings). For each of ``--control-seeds`` these are
read against the reference (the upper readings): the controls, which are
the program's own paths in the precision below the configuration's
float32, ``control_bf16`` (parameters and compute in bfloat16) and
``control_bf16_compute`` (float32 parameters, bfloat16 compute); and the
fault of half the batch left out, the mean taken over the rest, planted in
the reference. A control path that the program cannot trace gives a line
with its ``error`` and no numbers. A step that returns its state unchanged
reads 1 by construction and needs no run. One JSON line per reading. Everything runs in one process on the chip;
the benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONTROLS = {"control_bf16": {"param": "bfloat16", "compute": "bfloat16"},
            "control_bf16_compute": {"param": "float32",
                                     "compute": "bfloat16"}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from bench.lib import check
    from bench.lib.harness import CHECKED_STEPS, Program, cell_files
    from bench.lib.traffic import make_epoch
    from bench.lib.weights import weight_key

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 2
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    _, _, cfgspec, traffic = cell_files(args.workload)
    vocab, pad = cfgspec["model"]["vocab_size"], traffic["max_len"]
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctl_seeds = [int(s) for s in args.control_seeds.split(",") if s]

    def emit(kind, seed, numbers, **kw):
        print(json.dumps(dict(kind=kind, workload=args.workload, seed=seed,
                              **numbers, **kw)), flush=True)

    program = Program(cfgspec, traffic)
    controls = {name: Program(dict(copy.deepcopy(cfgspec), dtypes=dtypes),
                              traffic)
                for name, dtypes in CONTROLS.items()} if ctl_seeds else {}

    def readings(p, epoch, checked, seed):
        try:
            return p.checked_steps(epoch, checked, seed)
        finally:
            p.drop()
            gc.collect()

    def control_readings(p, epoch, checked, seed):
        """A control path the program cannot trace reads as an error: it
        has failed, and gives no number."""
        try:
            return readings(p, epoch, checked, seed)
        except TypeError as e:
            return {"error": str(e).splitlines()[0]}

    for seed in seeds + [s for s in ctl_seeds if s not in seeds]:
        epoch = make_epoch(traffic, vocab, seed)
        checked = epoch.pick(CHECKED_STEPS)
        batches = [epoch.batch(i) for i in checked]
        sls = [b.sl for b in batches]
        prog = readings(program, epoch, checked, seed) \
            if seed in seeds else None
        ctl = {name: control_readings(c, epoch, checked, seed)
               for name, c in controls.items()} if seed in ctl_seeds else {}
        ref = check.reference_readings(cfgspec, program.init_fn,
                                       weight_key(seed), batches, pad_to=pad)
        if prog is not None:
            emit("program", seed, check.gaps(prog, ref), sls=sls,
                 losses=prog["losses"], ref_losses=ref["losses"])
        for name, c in ctl.items():
            if "error" in c:
                emit(name, seed, {}, sls=sls, error=c["error"])
            else:
                emit(name, seed, check.gaps(c, ref), sls=sls,
                     losses=c["losses"])
        if ctl:
            half = check.reference_readings(
                cfgspec, program.init_fn, weight_key(seed), batches,
                rows=slice(0, traffic["batch"] // 2), pad_to=pad)
            emit("fault_half_batch", seed, check.gaps(half, ref), sls=sls)
    return 0


if __name__ == "__main__":
    sys.exit(main())
