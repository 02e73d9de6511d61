"""Benchmark harness: one function per paper table/figure + system tables.

``python -m benchmarks.run [--fast]`` prints ``name,us_per_call,derived``
CSV. ``--fast`` uses reduced epochs (CI-sized); the full runs are what
EXPERIMENTS.md cites. A bench that raises prints a ``BENCH_ERROR_`` row,
the rest still run, and the exit status is non-zero.
"""
from __future__ import annotations

import argparse
import os
import sys
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced epoch sizes (CI)")
    ap.add_argument("--only", default=None,
                    help="comma-separated substring filter on bench names")
    args = ap.parse_args()

    from benchmarks import dryrun_summary, kernels_bench, padding_waste, \
        paper_figures

    print("name,us_per_call,derived")
    groups = (paper_figures.ALL + kernels_bench.ALL + padding_waste.ALL
              + dryrun_summary.ALL)
    only = args.only.split(",") if args.only else None
    failed = []
    for fn in groups:
        if only and not any(o in fn.__name__ for o in only):
            continue
        try:
            fn(args.fast)
        except Exception as e:                      # noqa: BLE001
            print(f"BENCH_ERROR_{fn.__name__},0,{type(e).__name__}: {e}",
                  flush=True)
            traceback.print_exc(file=sys.stderr)
            failed.append(fn.__name__)
    if failed:
        print(f"benchmarks failed: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
