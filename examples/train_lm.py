"""Train a reduced-config LM end-to-end on CPU with the full production path
(config -> model registry -> optimizer -> async checkpointing -> resume).
The same launcher drives the 16x16-mesh dry-run configs.

  PYTHONPATH=src python examples/train_lm.py [--arch mixtral-8x7b]
"""
import argparse
import tempfile

from repro import compile_cache
from repro.launch.train import main as train_main

ap = argparse.ArgumentParser()
ap.add_argument("--arch", default="qwen2-1.5b")
ap.add_argument("--steps", type=int, default=30)
args = ap.parse_args()
compile_cache.enable()

ckpt_dir = tempfile.mkdtemp(prefix="repro_ckpt_")
losses = train_main(["--arch", args.arch, "--steps", str(args.steps),
                     "--batch", "8", "--seq", "64",
                     "--ckpt-dir", ckpt_dir, "--ckpt-every", "10"])
print(f"loss: {losses[0]:.3f} -> {losses[-1]:.3f} "
      f"({'descending ✓' if losses[-1] < losses[0] else 'NOT descending'})")
print(f"checkpoints in {ckpt_dir}")
