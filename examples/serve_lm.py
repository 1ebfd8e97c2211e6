"""Serve a model with batched requests: prefill + greedy/temperature decode.

Run:  PYTHONPATH=src python examples/serve_lm.py [--arch gemma3_12b]
"""
# This parent only builds the command line and never imports jax: a TPU
# chip belongs to one process at a time, and the child must get it.
import argparse
import subprocess
import sys

ap = argparse.ArgumentParser()
ap.add_argument("--arch", default="gemma3_12b")
ap.add_argument("--requests", default="8")
ap.add_argument("--gen", default="16")
args = ap.parse_args()

cmd = [sys.executable, "-m", "repro.launch.serve", "--arch", args.arch, "--smoke",
       "--requests", args.requests, "--gen", args.gen]
print("+", " ".join(cmd))
raise SystemExit(subprocess.call(cmd))
