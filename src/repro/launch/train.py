"""Production train launcher.

    PYTHONPATH=src python -m repro.launch.train --arch starcoder2-3b \
        --smoke --steps 50 --ckpt-dir /tmp/ck

``--smoke`` uses the structure-preserving reduced config (CPU-runnable);
without it the full assigned config is built. Either way the state lives on
one device — the launcher places nothing on a mesh — in float32 with AdamW,
16 bytes per parameter, so only configs whose training state and step fit
one device's memory run here (starcoder2-3b fits one 16 GB v5e only when
cut to two layers; see ``chip_smoke.py``). The SL schedule is logged and
SeqPoints are reported at the end, so every training run doubles as a
profiling artifact.
"""
from __future__ import annotations

import argparse
import sys
from typing import Optional

import numpy as np

from repro.configs import ModelConfig
from repro.train.trainer import Trainer


def build_trainer(cfg: ModelConfig, *, seq: int, batch: int, steps: int,
                  ckpt_dir: Optional[str] = None,
                  bucketed: bool = False) -> Trainer:
    """The launcher's trainer: ``cfg`` on a one-device mesh in float32,
    fed variable-SL LM documents padded to multiples of 16 tokens."""
    from repro.configs import (
        MeshConfig,
        OptimizerConfig,
        RunConfig,
        ShapeConfig,
        StepKind,
    )
    from repro.data.batching import DataIterator
    from repro.data.synthetic import lm_documents
    from repro.models import Runtime, build_model

    shape = ShapeConfig("cli", seq_len=seq, global_batch=batch,
                        step=StepKind.TRAIN)
    run = RunConfig(model=cfg, shape=shape,
                    mesh=MeshConfig(shape=(1,), axes=("data",)),
                    optimizer=OptimizerConfig(lr=3e-4, warmup_steps=10),
                    param_dtype="float32", compute_dtype="float32")
    model = build_model(cfg, Runtime.from_run(run))
    data = DataIterator(lm_documents(seq), samples_per_epoch=4096,
                        batch_size=batch, vocab_size=cfg.vocab_size,
                        granularity=16, bucketed=bucketed, seed=0)
    return Trainer(model, run, data, ckpt_dir=ckpt_dir, total_steps=steps)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--bucketed", action="store_true",
                    help="SL-bucketed batching (beyond-paper opt)")
    args = ap.parse_args()

    from repro.configs import get_model_config, smoke_config
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = smoke_config(args.arch) if args.smoke \
        else get_model_config(args.arch)
    if cfg.frontend is not None and not args.smoke:
        print("full multimodal configs need the frontend stub inputs; "
              "use --smoke or the dry-run", file=sys.stderr)
    trainer = build_trainer(cfg, seq=args.seq, batch=args.batch,
                            steps=args.steps, ckpt_dir=args.ckpt_dir,
                            bucketed=args.bucketed)
    rep = trainer.train(args.steps)
    print(f"arch={cfg.name} steps={rep.steps} "
          f"resumed_from={rep.resumed_from} "
          f"loss {rep.losses[0]:.3f}->{rep.losses[-1]:.3f} "
          f"median_step_ms={1e3*np.median(rep.step_times):.1f}")
    sp = trainer.seqpoints(error_threshold=0.05)
    print(f"seqpoints={sp.num_points} sls={sp.seq_lens} "
          f"error={100*sp.error:.2f}%")
    return 0


if __name__ == "__main__":
    sys.exit(main())
