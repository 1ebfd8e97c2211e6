"""Benchmark-record lane: validate every checked-in ``benchmarks/BENCH_*.json``
against its schema, hand-rolled (no jsonschema dependency).

Each benchmark driver owns a record shape; this script pins it so a schema
drift (a renamed key, a dropped section, a speedup that silently went below
1x) fails CI instead of rotting in the repo.  A ``BENCH_*.json`` file with
no registered schema is an error: new benchmarks register by adding one
``Bench`` row to the ``BENCHES`` table (schema + optional cross-field
checks) — nothing else to wire.

  PYTHONPATH=src python scripts/check_bench.py
"""

from __future__ import annotations

import glob
import json
import os
import sys
from typing import Callable, NamedTuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Schema:
    """Tiny structural validator: dicts map key -> sub-schema, types check
    with isinstance, tuples mean any-of, callables are predicates."""

    def __init__(self, spec):
        self.spec = spec

    def errors(self, value, path="$"):
        return list(_check(self.spec, value, path))


def _check(spec, value, path):
    if isinstance(spec, dict):
        if not isinstance(value, dict):
            yield f"{path}: expected object, got {type(value).__name__}"
            return
        for key, sub in spec.items():
            if key not in value:
                yield f"{path}: missing key '{key}'"
            else:
                yield from _check(sub, value[key], f"{path}.{key}")
    elif isinstance(spec, tuple):
        for sub in spec:
            if not list(_check(sub, value, path)):
                return
        yield f"{path}: {value!r} matches none of {spec}"
    elif isinstance(spec, type):
        ok = isinstance(value, spec)
        if spec is float:  # ints are acceptable where floats are expected
            ok = isinstance(value, (int, float)) and not isinstance(value, bool)
        if spec is int:
            ok = isinstance(value, int) and not isinstance(value, bool)
        if not ok:
            yield f"{path}: expected {spec.__name__}, got {type(value).__name__}"
    elif spec is None:
        if value is not None:
            yield f"{path}: expected null"
    elif callable(spec):
        try:
            ok, why = spec(value)
        except Exception as e:  # a predicate crash is a schema failure
            ok, why = False, f"predicate raised {e!r}"
        if not ok:
            yield f"{path}: {why}"
    else:
        raise TypeError(f"bad schema node at {path}: {spec!r}")


def positive(v):
    return (isinstance(v, (int, float)) and not isinstance(v, bool) and v > 0,
            f"expected a positive number, got {v!r}")


def fraction(v):
    return (isinstance(v, (int, float)) and 0 <= v <= 1,
            f"expected a value in [0, 1], got {v!r}")


def nonempty_list(v):
    return (isinstance(v, list) and len(v) > 0, "expected a non-empty list")


_SUBPLAN = {"dataflow": str, "block": (list, None), "strip": int}

TRAIN_STEP_SCHEMA = Schema({
    "config": {"tokens": int, "d_model": int, "d_ff": int, "iters": int,
               "interpret": bool},
    "layers": nonempty_list,
    "walltime_s": {"pallas": positive, "pallas_streamed": positive,
                   "pallas_copy_bwd": positive, "xla": positive},
    "hbm_bytes_est": {"bwd_transpose_free": positive, "bwd_via_copy": positive,
                      "plan_strips": positive, "forced_streamed": positive},
    "quant": nonempty_list,
    "strip_showcase": nonempty_list,
    "mesh_composition": (list, None),
})


def extra_train_step_checks(rec) -> list[str]:
    """Per-layer quant columns: verdicts and gate errors must be coherent."""
    errors = []
    for row in rec["quant"]:
        name = row.get("name", "?")
        if row.get("qdtype") not in ("int8", "fp8", "bf16"):
            errors.append(f"quant[{name}]: verdict {row.get('qdtype')!r} is "
                          "not a tuned outcome")
        fb = row.get("fwd_hbm_bytes", {})
        if fb.get("quant", 0) >= fb.get("bf16", 0):
            errors.append(f"quant[{name}]: quantized fwd HBM bytes not below "
                          "bf16 — the 1-byte weight stream saved nothing")
        for qd, err in row.get("gate_errors", {}).items():
            if row.get("qdtype") == qd and err > row.get("budget", 0):
                errors.append(
                    f"quant[{name}]: verdict {qd} but its gate error {err} "
                    f"exceeds the budget {row.get('budget')}")
    return errors

_LANE = {"walltime_s": positive, "tokens": positive,
         "tokens_per_s": positive, "decode_steps": positive}

SERVE_SCHEMA = Schema({
    "config": {"profile": str, "requests": positive, "slots": positive,
               "block_size": positive, "prompt_len": list, "gen_len": list,
               "arrival_rate": float, "seed": int,
               "model": {"d_model": int, "d_ff": int, "num_layers": int,
                         "num_heads": int, "num_kv_heads": int,
                         "head_dim": int, "vocab_size": int}},
    "continuous": {**_LANE, "prefills": positive,
                   "slot_utilization": fraction,
                   "bucket_histogram": dict},
    "fixed_batch": {**_LANE, "row_steps": positive},
    "speedup_tokens_per_s": positive,
    "faulted": {"spec": str, "walltime_s": positive, "requests": positive,
                "completed": int, "completed_tokens": int,
                "emitted_tokens": int, "goodput_tokens_per_s": positive,
                "throughput_tokens_per_s": positive, "statuses": dict,
                "preemptions": int, "replays": int,
                "faults_injected": dict, "streams_match_clean": bool,
                "crashes": int},
})


def extra_serve_checks(rec) -> list[str]:
    """Cross-field relations the flat schema can't express."""
    errors = []
    cont, fixed = rec["continuous"], rec["fixed_batch"]
    if cont["tokens"] != fixed["tokens"]:
        errors.append(
            f"continuous decoded {cont['tokens']} tokens but fixed-batch "
            f"{fixed['tokens']} — not the same workload")
    if rec["speedup_tokens_per_s"] <= 1.0:
        errors.append(
            f"checked-in speedup is {rec['speedup_tokens_per_s']:.3f}x — "
            "continuous batching must beat the fixed-batch baseline")
    if fixed["row_steps"] < fixed["tokens"]:
        errors.append("fixed_batch.row_steps < useful tokens (impossible)")
    buckets = {int(k) for k in cont["bucket_histogram"]}
    if any(b > rec["config"]["slots"] for b in buckets):
        errors.append(
            f"bucket histogram {sorted(buckets)} exceeds slot capacity "
            f"{rec['config']['slots']}")
    ft = rec["faulted"]
    if ft["crashes"] != 0:
        errors.append(f"faulted.crashes is {ft['crashes']} — the scheduler "
                      "must degrade, never crash")
    if not ft["streams_match_clean"]:
        errors.append("faulted: a completed stream diverged from the clean "
                      "replay — preempt-and-replay determinism broken")
    # goodput <= clean, stated structurally (token counts / same-run rates)
    # rather than as cross-run wall-clock, which CPU timing noise can flip:
    # faults can only lose completed work, and replayed/truncated work is
    # never goodput.
    if ft["completed_tokens"] > cont["tokens"]:
        errors.append(
            f"faulted completed {ft['completed_tokens']} tokens but the "
            f"clean run only has {cont['tokens']} — injected faults cannot "
            "create completed work")
    if ft["goodput_tokens_per_s"] > ft["throughput_tokens_per_s"]:
        errors.append(
            "faulted goodput exceeds the same run's total throughput — "
            "replayed/failed work counted as goodput")
    if ft["completed_tokens"] > ft["emitted_tokens"]:
        errors.append(
            f"faulted: {ft['completed_tokens']} completed tokens exceed the "
            f"{ft['emitted_tokens']} emitted — accounting is wrong")
    if sum(ft["statuses"].values()) != ft["requests"]:
        errors.append(
            f"faulted.statuses {ft['statuses']} does not account for every "
            f"request ({ft['requests']})")
    if ft["completed"] < 1:
        errors.append("faulted: nothing completed — degradation is total")
    if ft["replays"] > ft["preemptions"]:
        errors.append(
            f"faulted: {ft['replays']} replays exceed {ft['preemptions']} "
            "preemptions (each replay must follow a preemption)")
    return errors


_ATTN_VARIANT = {"walltime_s": positive, "hbm_bytes": positive,
                 "vmem_bytes": positive}

ATTN_SCHEMA = Schema({
    "config": {"seq": int, "kv": int, "heads": int, "kv_heads": int,
               "head_dim": int, "group": int, "iters": int,
               "interpret": bool, "buckets": nonempty_list},
    "prefill": {"q": {**_ATTN_VARIANT, "block": nonempty_list},
                "kv": {**_ATTN_VARIANT, "block": nonempty_list}},
    "decode": dict,
    "planned": {"sweep": str, "block": nonempty_list, "source": str,
                "decode_kinds": dict},
})


def extra_attn_checks(rec) -> list[str]:
    """The analytical orderings the schedule family exists to exploit."""
    errors = []
    pf = rec["prefill"]
    if pf["q"]["block"] == pf["kv"]["block"]:
        if pf["kv"]["hbm_bytes"] >= pf["q"]["hbm_bytes"]:
            errors.append(
                "kv-stationary must move less HBM than q-stationary at the "
                "same blocks on a GQA prefill shape (K/V resident, Q streams)")
        if pf["kv"]["vmem_bytes"] <= pf["q"]["vmem_bytes"]:
            errors.append(
                "kv-stationary must hold more VMEM than q-stationary "
                "(whole-rows accumulator slab) — residency math drifted")
    for b, row in rec["decode"].items():
        for kind in ("paged", "gather"):
            if kind not in row:
                errors.append(f"decode[{b}]: missing kind '{kind}'")
                continue
            errors += [f"decode[{b}].{kind}: {m}"
                       for m in Schema(_ATTN_VARIANT).errors(row[kind])]
        if ("paged" in row and "gather" in row
                and row["paged"]["hbm_bytes"] >= row["gather"]["hbm_bytes"]):
            errors.append(
                f"decode[{b}]: the in-place paged kernel must read less HBM "
                "than the densifying gather (it skips the 3x cache copy)")
    if rec["planned"]["sweep"] not in ("q", "kv"):
        errors.append(f"planned.sweep {rec['planned']['sweep']!r} unknown")
    bad = {b: k for b, k in rec["planned"]["decode_kinds"].items()
           if k not in ("paged", "gather")}
    if bad:
        errors.append(f"planned.decode_kinds has unknown kinds: {bad}")
    if {int(b) for b in rec["decode"]} != set(rec["config"]["buckets"]):
        errors.append("decode buckets don't match config.buckets")
    return errors


_SSM_VARIANT = {"chunk": int, "walltime_s": positive, "hbm_bytes": positive,
                "vmem_bytes": positive}

SSM_SCHEMA = Schema({
    "config": {"batch": int, "seq": int, "heads": int, "key_dim": int,
               "val_dim": int, "post_update": bool, "iters": int,
               "interpret": bool, "buckets": nonempty_list},
    "prefill": dict,
    "decode": dict,
    "planned": {"sweep": str, "chunk": int, "source": str,
                "decode_kinds": dict},
})


def extra_ssm_checks(rec) -> list[str]:
    """The analytical orderings the scan schedule family exists to exploit."""
    errors = []
    for chunk, row in rec["prefill"].items():
        for sweep in ("state", "out"):
            if sweep not in row:
                errors.append(f"prefill[{chunk}]: missing sweep '{sweep}'")
                continue
            errors += [f"prefill[{chunk}].{sweep}: {m}"
                       for m in Schema(_SSM_VARIANT).errors(row[sweep])]
        if "state" in row and "out" in row:
            if row["state"]["hbm_bytes"] >= row["out"]["hbm_bytes"]:
                errors.append(
                    f"prefill[{chunk}]: state-stationary must move less HBM "
                    "than the out-streamed sweep at the same chunk (the "
                    "state never round-trips) — traffic math drifted")
            if row["state"]["vmem_bytes"] < row["out"]["vmem_bytes"]:
                errors.append(
                    f"prefill[{chunk}]: state-stationary must hold at least "
                    "as much VMEM (the whole state slab stays resident)")
    for b, row in rec["decode"].items():
        for kind in ("fused", "einsum"):
            if kind not in row:
                errors.append(f"decode[{b}]: missing kind '{kind}'")
                continue
            errors += [f"decode[{b}].{kind}: {m}"
                       for m in Schema({"walltime_s": positive,
                                        "hbm_bytes": positive,
                                        "vmem_bytes": positive,
                                        }).errors(row[kind])]
        if ("fused" in row and "einsum" in row
                and row["fused"]["hbm_bytes"] >= row["einsum"]["hbm_bytes"]):
            errors.append(
                f"decode[{b}]: the fused step kernel must read less HBM "
                "than the jnp recurrence (no k v^T intermediate round-trip)")
    if rec["planned"]["sweep"] not in ("state", "out"):
        errors.append(f"planned.sweep {rec['planned']['sweep']!r} unknown")
    if rec["planned"]["chunk"] <= 0:
        errors.append(f"planned.chunk {rec['planned']['chunk']} not positive")
    bad = {b: k for b, k in rec["planned"]["decode_kinds"].items()
           if k not in ("fused", "einsum")}
    if bad:
        errors.append(f"planned.decode_kinds has unknown kinds: {bad}")
    if {int(b) for b in rec["decode"]} != set(rec["config"]["buckets"]):
        errors.append("decode buckets don't match config.buckets")
    return errors


_QLANE = {"tokens": positive, "decode_hbm_bytes": positive}

QUANT_SCHEMA = Schema({
    "config": {"profile": str, "requests": positive, "slots": positive,
               "prompt_len": list, "gen_len": list, "arrival_rate": float,
               "seed": int,
               "model": {"d_model": int, "d_ff": int, "num_layers": int,
                         "vocab_size": int}},
    "walltime_s": positive,
    "tokens_per_s": positive,
    "bucket_histogram": dict,
    "quant": {"dtypes": nonempty_list, "budget": positive,
              "verdicts": dict, "max_qerror": positive},
    "lanes": {"bf16": _QLANE, "quant": _QLANE},
    "decode_hbm_ratio": positive,
})


def extra_quant_checks(rec) -> list[str]:
    """Cross-lane invariants: the quant lane must be the same workload as
    the bf16 lane and actually buy decode bandwidth, and the accuracy-gate
    metadata recorded with the plan must be coherent."""
    errors = []
    bf16, quant = rec["lanes"]["bf16"], rec["lanes"]["quant"]
    if bf16["tokens"] != quant["tokens"]:
        errors.append(
            f"lanes decoded different token counts (bf16 {bf16['tokens']} "
            f"vs quant {quant['tokens']}) — not the same workload")
    if quant["decode_hbm_bytes"] >= bf16["decode_hbm_bytes"]:
        errors.append(
            f"quant decode HBM {quant['decode_hbm_bytes']:,} B is not below "
            f"the bf16 lane's {bf16['decode_hbm_bytes']:,} B — quantization "
            "bought nothing")
    ratio = quant["decode_hbm_bytes"] / bf16["decode_hbm_bytes"]
    if abs(rec["decode_hbm_ratio"] - ratio) > 1e-9:
        errors.append(
            f"decode_hbm_ratio {rec['decode_hbm_ratio']} disagrees with the "
            f"lanes' quotient {ratio}")
    if rec["decode_hbm_ratio"] > 0.6:
        errors.append(
            f"decode_hbm_ratio {rec['decode_hbm_ratio']:.3f} above the 0.6 "
            "bar — a 1-byte weight stream should roughly halve decode GEMM "
            "traffic at the bench profile")
    q = rec["quant"]
    if q["max_qerror"] > q["budget"]:
        errors.append(
            f"max_qerror {q['max_qerror']} exceeds the recorded budget "
            f"{q['budget']} — a plan shipped past its own accuracy gate")
    bad = set(q["dtypes"]) - {"int8", "fp8"}
    if bad:
        errors.append(f"unknown quant dtypes {sorted(bad)}")
    bad = set(q["verdicts"]) - {"int8", "fp8", "bf16"}
    if bad:
        errors.append(f"unknown verdict dtypes {sorted(bad)}")
    if not any(k in q["verdicts"] for k in ("int8", "fp8")):
        errors.append(
            f"no quantized verdicts in {q['verdicts']} — every layer fell "
            "back to bf16 at the bench profile")
    buckets = {int(b) for b in rec["bucket_histogram"]}
    if any(b > rec["config"]["slots"] for b in buckets):
        errors.append(
            f"bucket histogram {sorted(buckets)} exceeds slot capacity "
            f"{rec['config']['slots']}")
    return errors


class Bench(NamedTuple):
    """One registered benchmark record: the filename it pins, its structural
    schema, and optional cross-field checks the flat schema can't express."""

    filename: str
    schema: Schema
    extra: Callable[[dict], list[str]] | None = None


BENCHES = (
    Bench("BENCH_train_step.json", TRAIN_STEP_SCHEMA, extra_train_step_checks),
    Bench("BENCH_serve.json", SERVE_SCHEMA, extra_serve_checks),
    Bench("BENCH_attn.json", ATTN_SCHEMA, extra_attn_checks),
    Bench("BENCH_ssm.json", SSM_SCHEMA, extra_ssm_checks),
    Bench("BENCH_quant.json", QUANT_SCHEMA, extra_quant_checks),
)

VALIDATORS = {b.filename: b for b in BENCHES}


def main() -> int:
    errors: list[str] = []
    paths = sorted(glob.glob(os.path.join(ROOT, "benchmarks", "BENCH_*.json")))
    if not paths:
        print("BENCH CHECK FAILED: no benchmarks/BENCH_*.json records found")
        return 1
    for path in paths:
        name = os.path.basename(path)
        bench = VALIDATORS.get(name)
        if bench is None:
            errors.append(f"{name}: no Bench row registered in check_bench.py")
            continue
        try:
            with open(path) as f:
                rec = json.load(f)
        except json.JSONDecodeError as e:
            errors.append(f"{name}: invalid JSON — {e}")
            continue
        errs = bench.schema.errors(rec)
        if not errs and bench.extra is not None:
            errs = [f"{name}: {msg}" for msg in bench.extra(rec)]
        else:
            errs = [f"{name}: {msg}" for msg in errs]
        errors += errs
        print(f"checked {name}" + (f" — {len(errs)} error(s)" if errs else ""))
    if errors:
        print("\n".join(["", "BENCH CHECK FAILED:"] + errors))
        return 1
    print(f"bench check OK ({len(paths)} record(s))")
    return 0


if __name__ == "__main__":
    sys.exit(main())
