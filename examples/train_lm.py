"""Train a language model end-to-end with the full framework stack
(data pipeline -> model -> AdamW -> checkpointing -> fault-tolerant loop).

Default is a CPU-sized run; `--preset 100m` trains a ~100M-param qwen3-style
model for a few hundred steps (sized for a TPU host; takes hours on 1 CPU).

Run:  PYTHONPATH=src python examples/train_lm.py [--preset 100m] [--steps N]
"""
# This parent only builds the command line and never imports jax: a TPU
# chip belongs to one process at a time, and the child must get it.
import argparse
import subprocess
import sys

PRESETS = {
    "tiny": ["--arch", "qwen3_4b", "--smoke", "--steps", "60",
             "--global-batch", "8", "--seq", "64", "--lr", "1e-3"],
    "20m": ["--arch", "qwen3_4b", "--smoke", "--d-model", "256", "--layers", "4",
            "--steps", "200", "--global-batch", "8", "--seq", "128", "--lr", "6e-4"],
    "100m": ["--arch", "qwen3_4b", "--smoke", "--d-model", "640", "--layers", "10",
             "--steps", "300", "--global-batch", "16", "--seq", "256", "--lr", "4e-4"],
}

ap = argparse.ArgumentParser()
ap.add_argument("--preset", default="tiny", choices=sorted(PRESETS))
ap.add_argument("--steps", default=None)
ap.add_argument("--fail-at", default=None, help="inject a node failure at step N")
args = ap.parse_args()

cmd = [sys.executable, "-m", "repro.launch.train"] + PRESETS[args.preset]
if args.steps:
    cmd += ["--steps", args.steps]
if args.fail_at:
    cmd += ["--fail-at", args.fail_at]
print("+", " ".join(cmd))
raise SystemExit(subprocess.call(cmd))
