"""One traced run of a benchmark cell, broken down by the program's named
scopes and host spans (``bench/scope_reduce.py``).

    python scripts/scope_breakdown.py --workload qwen3_4b.decode_heavy \
        --seed 7 --seconds 45 --out chiprun_out/scopes.json

Runs ``bench/run.py``'s traced run (``--trace 1``) in-process and prints
the harness's result line, then writes to ``--out``: device ms per call of
each program under each scope, the top-level ops per call as the
harness's breakdown labels them, the traced window's idle gaps labelled
by the host span over them, how far each ``ServeStats`` span lies from its
host annotation, and the run's end-to-end numbers (a traced run's, so the
profiler's cost is in them).

The harness hands its trace to no metric reader yet, so this script takes
the parsed trace from ``trace_reduce.reduce``'s arguments, and the
scheduler's jitted steps from ``harness.Program.scheduler``: after the
run it compiles each signature the window ran again (the persistent
compile cache holds them) for the op_names of its instructions.  Needs a
TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from collections import defaultdict
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


class StepSignatures:
    """Wraps a scheduler's jitted prefill and decode steps to note the
    argument specs of each signature they are called with."""

    def __init__(self, sched):
        self.seen: dict[tuple, tuple] = {}
        sched._prefill = self._watch("prefill", sched._prefill)
        sched._decode = self._watch("decode", sched._decode)

    def _watch(self, kind, fn):
        import jax

        def call(*args):
            key = (kind,) + tuple(getattr(a, "shape", None) for a in args[1:])
            if key not in self.seen:
                self.seen[key] = (fn, jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
                    args))
            return fn(*args)
        return call

    def op_names(self, keep) -> dict[str, dict[str, str]]:
        """``scope_reduce.op_names`` per program kind, over the compiled
        text of each signature ``keep(key)`` admits."""
        from bench import scope_reduce

        texts = defaultdict(list)
        for key, (fn, specs) in self.seen.items():
            if keep(key):
                texts[key[0]].append(fn.lower(*specs).compile().as_text())
        return {k: scope_reduce.op_names(*v) for k, v in texts.items()}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args(argv)

    from bench import harness, scope_reduce, trace_reduce, window

    seen: dict = {}
    reduce, select, scheduler = trace_reduce.reduce, window.select, harness.Program.scheduler

    def keep_reduce(pd, anchor, win, *a, **kw):
        seen.update(pd=pd, anchor=anchor, t_end=kw.get("t_end"))
        seen["reduced"] = reduce(pd, anchor, win, *a, **kw)
        return seen["reduced"]

    def keep_select(stats, results, requests, *a, **kw):
        win = select(stats, results, requests, *a, **kw)
        seen.update(stats=stats, requests={r.rid: r for r in requests}, win=win)
        return win

    def keep_scheduler(self, params):
        sched = scheduler(self, params)
        seen.update(steps=StepSignatures(sched), bucket=sched.prompt_bucket)
        return sched

    trace_reduce.reduce, window.select = keep_reduce, keep_select
    harness.Program.scheduler = keep_scheduler
    result = harness.run(args.workload, args.seed, args.seconds, True,
                         root=ROOT, t_start=T_START)
    harness.emit(result)

    pd, anchor, win, stats = seen["pd"], seen["anchor"], seen["win"], seen["stats"]
    reqs = seen["requests"]
    decode_rows = set(stats.bucket_per_step[win.steps[0]:win.steps[1]])
    prompt_rows = {seen["bucket"](len(reqs[rid].prompt)) for rid in win.prefilled()}
    # keys: ("prefill", tokens (1, bucket), ...), ("decode", pool, pool, table (rows, nb), ...)
    names = seen["steps"].op_names(
        lambda k: k[1][1] in prompt_rows if k[0] == "prefill" else k[3][0] in decode_rows)
    a_ns = scope_reduce.anchor_ns(pd)
    t0 = a_ns + (win.t0 - anchor) * 1e9
    t1 = a_ns + (min(win.t1, seen["t_end"] or win.t1) - anchor) * 1e9
    times = scope_reduce.scope_times(pd, t0, t1, names)
    spans = scope_reduce.to_trace_clock(stats.spans, anchor, a_ns)
    offsets = scope_reduce.clock_offsets_us(spans, scope_reduce.host_annotations(pd))
    gaps = scope_reduce.idle_gaps(pd, t0, t1, spans)
    long_gaps = [g for g in gaps if g[1] > 100e-6]
    red = seen["reduced"]
    prompt = sum(len(reqs[rid].prompt) for rid in win.prefilled())
    out = {
        "workload": args.workload, "seed": args.seed,
        "calls": times.calls,
        "scope_ms_per_call": {k: {s: 1e3 * v / times.calls[k]
                                  for s, v in sorted(d.items(), key=lambda kv: -kv[1])}
                              for k, d in times.seconds.items()},
        "program_ms": {k: red.program_ms(k) for k in times.calls},
        "attn_kv_ms.decode": red.non_gemm_ms("decode"),
        # the breakdown's top-level ops, per call of their program
        "ops_ms_per_call": {k: v * 1e3 / times.calls.get(k.split(":")[0], 1)
                            for k, v in sorted(red.ops.items(), key=lambda kv: -kv[1])[:16]},
        "gaps_over_100us": len(long_gaps),
        "gaps_over_100us_without_span": sum(not g[0].startswith("serve.")
                                            for g in long_gaps),
        "gap_s_by_label": _sum_by(gaps),
        "span_annotation_offset_us": {"matched": len(offsets),
                                      "max": max(offsets, default=None)},
        "window_s": win.seconds,
        "output_tokens_per_s": win.tokens / win.seconds,
        "prompt_tokens_per_s": prompt / win.seconds,
        "host_seconds": stats.host_seconds(),
    }
    print(f"scopes: {json.dumps(out)}", file=sys.stderr)
    args.out.parent.mkdir(parents=True, exist_ok=True)
    args.out.write_text(json.dumps(out, indent=1))
    return 0


def _sum_by(gaps) -> dict[str, float]:
    out: dict[str, float] = {}
    for label, s in gaps:
        out[label] = out.get(label, 0.0) + s
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


if __name__ == "__main__":
    sys.exit(main())
