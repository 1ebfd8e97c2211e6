"""Readings for a cell's correctness limit: the program's and the control's.

    python bench/control.py --workload qwen3_4b.prefill_heavy --seeds 1-12

For each seed, in one process: the benchmark's weights for that seed, a
trace of the cell's mix at the cell's load (every slot busy; enough
requests that the sample holds as many served tokens as a run checks),
served through the same scheduler, plan and compiled steps as a run; then,
on the sample a run would draw, the widest gap of the program's served
tokens under the plain reference (the lower reading) and the widest gap
of the tokens that the control ranks first (the upper reading).  It
exits non-zero unless, on every seed, the program's reading is within the
cell's ``max_logit_gap`` limit and the control's is over it.  The
control is the reference one precision below the configuration's
bfloat16: float8 (e4m3) operands in every projection, bfloat16 activations
elsewhere (``references/*.py``, ``control=True``).  It needs a TPU, as a run does.
The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def seeds(spec: str) -> list[int]:
    out = []
    for part in spec.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def readings(root: Path, cell_name: str, seed_list: list[int], *,
             require_tpu: bool = True, requests_per_slot: int = 3) -> list[dict]:
    from bench import correct, harness

    cell = harness.load_cell(root, cell_name)
    if require_tpu:
        harness.check_device(cell.chips)
    prog = harness.Program(root, cell)
    c = cell.config
    make_forward = harness.load_reference(root, c["reference"])
    ref, ctl = make_forward(c), make_forward(c, control=True)
    vocab = int(c["vocab_size"])
    n = prog.capacity * requests_per_slot
    out = []
    for seed in seed_list:
        t0 = time.perf_counter()
        params = prog.weights(seed)
        sched = prog.scheduler(params)
        reqs = [prog.Request(rid=i, prompt=p, max_new=g)
                for i, (p, g) in enumerate(cell.mix.requests(seed, n, vocab))]
        results, _ = sched.run(reqs)
        del sched
        served = {r.rid: results[r.rid].tokens for r in reqs
                  if results[r.rid].tokens is not None}
        chk = correct.check(ref, params, reqs, served, seed,
                            int(cell.params["check_tokens"]), vocab, control=ctl)
        out.append({"seed": seed, "program": chk.max_gap, "control": chk.control_gap,
                    "requests": chk.requests, "tokens": chk.tokens,
                    "seconds": time.perf_counter() - t0})
        print(json.dumps({"cell": cell_name, **out[-1]}), flush=True)
        del params
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 1-12 or 3,5,9")
    ap.add_argument("--requests-per-slot", type=int, default=3,
                    help="requests served per slot: enough that the sample "
                         "holds the cell's check_tokens")
    args = ap.parse_args(argv)
    from bench import harness

    try:
        rows = readings(ROOT, args.workload, seeds(args.seeds),
                        requests_per_slot=args.requests_per_slot)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    limit = harness.load_cell(ROOT, args.workload).params["limits"]["max_logit_gap"]
    v = verdict(rows, limit)
    print(json.dumps({"cell": args.workload, **v}), flush=True)
    return 0 if v["separated"] else 1


def verdict(rows: list[dict], limit: float) -> dict:
    """The lower and upper readings, and whether the cell's limit tells
    the two apart on every seed: the program within it, the control over."""
    bad = [r["seed"] for r in rows
           if not (r["program"] <= limit < r["control"])]
    return {"lower": max(r["program"] for r in rows),
            "upper": min(r["control"] for r in rows), "limit": limit,
            "seeds": len(rows), "not_separated": bad, "separated": not bad}


if __name__ == "__main__":
    sys.exit(main())
