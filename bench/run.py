"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload qwen3_4b.prefill_heavy --seed 7 \
        --seconds 30 --trace 0

The cell, its configuration, its traffic mix and its metrics are found by
name from ``BENCHMARK.json`` at the root of the checkout (see
``bench/harness.py``).  The run needs a TPU with as many chips as the cell
asks for: without one it exits with a non-zero code and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# the TPU runtime logs under /tmp unless told otherwise; a run writes only
# inside its checkout and the directories it is given
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="a cell of BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True,
                    help="inputs and weights are made from it (>= 0)")
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="0: end-to-end metrics; 1: per-layer metrics from a "
                         "device trace of the same run")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    if args.seconds <= 0:
        ap.error("--seconds must be > 0")
    return args


def main(argv: list[str] | None = None) -> int:
    args = parse_args(argv)
    from bench import harness

    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), root=ROOT, t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
