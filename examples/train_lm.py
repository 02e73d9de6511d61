"""End-to-end driver: train a ~110M-param LM for a few hundred steps on CPU
with variable-SL batches, checkpoints + auto-resume, and SeqPoint logging.

    PYTHONPATH=src python examples/train_lm.py --steps 300

Kill it mid-run and re-run: it resumes from the last checkpoint.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.configs import (
    MeshConfig,
    ModelConfig,
    OptimizerConfig,
    RunConfig,
    ShapeConfig,
    StepKind,
)
from repro.data.batching import DataIterator
from repro.data.synthetic import lm_documents
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Runtime, build_model
from repro.train.trainer import Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--d-model", type=int, default=768)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = ModelConfig(
        name="lm-110m", family="dense", num_layers=args.layers,
        d_model=args.d_model, d_ff=4 * args.d_model, vocab_size=32_000,
        num_heads=args.d_model // 64, num_kv_heads=args.d_model // 64 // 2)
    from repro.perfmodel.model_flops import param_count
    print(f"model: {param_count(cfg)/1e6:.0f}M params (non-embedding)")

    shape = ShapeConfig("train", seq_len=args.seq, global_batch=args.batch,
                        step=StepKind.TRAIN)
    run = RunConfig(model=cfg, shape=shape,
                    mesh=MeshConfig(shape=(1,), axes=("data",)),
                    optimizer=OptimizerConfig(lr=3e-4, warmup_steps=20),
                    param_dtype="float32", compute_dtype="float32")
    model = build_model(cfg, Runtime.from_run(run))
    data = DataIterator(lm_documents(args.seq), samples_per_epoch=4096,
                        batch_size=args.batch, vocab_size=cfg.vocab_size,
                        granularity=32, seed=0)
    trainer = Trainer(model, run, data, ckpt_dir=args.ckpt_dir,
                      ckpt_every=50, total_steps=args.steps)
    report = trainer.train(args.steps)
    print(f"steps={report.steps} resumed_from={report.resumed_from} "
          f"loss {report.losses[0]:.3f} -> {report.losses[-1]:.3f} "
          f"median_step={1e3*np.median(report.step_times):.0f}ms "
          f"stragglers={report.stragglers}")
    sp = trainer.seqpoints(error_threshold=0.05)
    print(f"SeqPoints for this run: {sp.num_points} SLs {sp.seq_lens} "
          f"(error {100*sp.error:.2f}%)")


if __name__ == "__main__":
    main()
