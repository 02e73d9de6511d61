import os
# CPU-only compile tool: virtual host devices, never the accelerator
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
os.environ["JAX_PLATFORMS"] = "cpu"

"""§Perf hillclimb runner: re-derive a cell's roofline terms under an
optimization and record baseline vs optimized (results/perf_<cell>.json).

    python -m repro.launch.perf --cell deepseek-v3-671b/train_4k \
        --opt moe_full_ep --hypothesis "..."
"""
import argparse
import json
import sys
from typing import Any, Dict

from repro.configs import SINGLE_POD, get_model_config, get_shape
from repro.launch.dryrun import RESULTS_DIR, run_cell
from repro.launch.mesh import make_mesh

OPTS: Dict[str, Dict[str, Any]] = {
    "moe_full_ep": {"moe_full_ep": True},
    "dp_only": {"parallelism": "dp_only"},
    "moe_full_ep_serve": {"moe_full_ep": True, "fsdp": False},
    "no_fsdp": {"fsdp": False},
    "nmicro4": {"microbatches": 4},
    "save_boundaries": {"remat": "save_boundaries"},
    "moe_full_ep_zero1": {"moe_full_ep": True, "zero_stage": 1},
}


def terms_of(rec: dict) -> dict:
    t = rec["terms"]
    bound = max(t.values())
    return {"compute_s": t["compute_s"], "memory_s": t["memory_s"],
            "collective_s": t["collective_s"], "bound_s": bound,
            "dominant": rec["dominant"],
            "fraction": rec["roofline_fraction"],
            "useful_flops_ratio": rec["useful_flops_ratio"],
            "collectives": rec["collectives"]}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)         # arch/shape
    ap.add_argument("--opt", required=True, choices=sorted(OPTS))
    ap.add_argument("--hypothesis", default="")
    ap.add_argument("--tag", default=None)
    args = ap.parse_args()
    arch, shape_name = args.cell.split("/")

    mesh = make_mesh(SINGLE_POD)
    base = run_cell(arch, shape_name, SINGLE_POD, mesh, "roofline")
    if base["status"] != "ok":
        print("baseline failed:", base.get("error"), file=sys.stderr)
        return 1
    opt = run_cell(arch, shape_name, SINGLE_POD, mesh, "roofline",
                   **OPTS[args.opt])
    if opt["status"] != "ok":
        print("optimized failed:", opt.get("error"), file=sys.stderr)
        print(opt.get("traceback", ""), file=sys.stderr)
        return 1

    b, o = terms_of(base), terms_of(opt)
    out = {
        "cell": f"{arch}-{shape_name}-{args.opt}",
        "arch": arch, "shape": shape_name, "opt": args.opt,
        "hypothesis": args.hypothesis,
        "baseline": b, "optimized": o,
        "speedup": b["bound_s"] / max(o["bound_s"], 1e-12),
        "confirmed": o["bound_s"] < b["bound_s"],
    }
    os.makedirs(RESULTS_DIR, exist_ok=True)
    tag = args.tag or f"{arch}_{shape_name}_{args.opt}"
    path = os.path.join(RESULTS_DIR, f"perf_{tag}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: v for k, v in out.items()
                      if k not in ("baseline", "optimized")}, indent=1))
    print("baseline :", json.dumps({k: round(v, 4) if isinstance(v, float)
                                    else v for k, v in b.items()
                                    if k != "collectives"}))
    print("optimized:", json.dumps({k: round(v, 4) if isinstance(v, float)
                                    else v for k, v in o.items()
                                    if k != "collectives"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
