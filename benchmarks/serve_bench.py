"""Serving benchmark: continuous batching vs the fixed-batch baseline.

Replays a synthetic Poisson trace (mixed prompt/generation lengths) through
the ``launch.scheduler`` continuous-batching runtime and through the legacy
fixed-batch loop, and reports tokens/s, slot utilization, and the decode
bucket histogram.  Both paths get one untimed
warm-up replay first so compile time never pollutes the comparison.

The default ``--profile bench`` model (d=512, 4 layers) is deliberately
compute-bound: that is the regime continuous batching targets.  At toy
``--profile smoke`` scale a decode step costs microseconds and Python
dispatch dominates, which rewards the fixed batch's fewer-but-fatter steps
— a scheduling artifact, not a serving result.

  PYTHONPATH=src python benchmarks/serve_bench.py
  PYTHONPATH=src python benchmarks/serve_bench.py --json benchmarks/BENCH_serve.json
  PYTHONPATH=src python benchmarks/serve_bench.py --dry-run   # CI smoke

``--dry-run`` is the CI serving lane's functional smoke: tiny workload,
no timing gates — it asserts the scheduler invariants (every admitted
request finishes with exactly ``max_new`` tokens, the block allocator is
fully restored, streams are bitwise identical to per-request sequential
decode) and that decode steps actually dispatch through the tuned
batch-bucket CMU sub-plans (a recorder on ``LayerPlan.decode_plan``).
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

import jax


def build_model(profile: str):
    """The benchmark model.  ``bench`` scales the smoke config up to a
    compute-bound size; ``smoke`` is the tiny CI config."""
    from repro.models import Model, get_config

    cfg = get_config("qwen3_4b", smoke=True)
    if profile == "bench":
        cfg = cfg.replace(d_model=512, d_ff=2048, num_heads=8,
                          num_kv_heads=4, head_dim=64, num_layers=4)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def run_continuous(model, params, trace, args):
    from repro.launch.scheduler import ServeScheduler

    def once():
        sched = ServeScheduler(
            model, params, capacity=args.slots, block_size=args.block_size,
            max_total_len=args.max_prompt + args.max_gen)
        t0 = time.perf_counter()
        results, stats = sched.run(trace)
        return results, stats, time.perf_counter() - t0, sched

    once()  # warm-up: compile every (prompt-bucket, batch-bucket) signature
    results, stats, wall, sched = once()
    check_invariants(trace, results, stats, sched)
    return results, {
        "walltime_s": wall,
        "tokens": stats.tokens,
        "tokens_per_s": stats.tokens / max(wall, 1e-9),
        "decode_steps": stats.steps,
        "prefills": stats.prefills,
        "slot_utilization": stats.slot_utilization,
        "bucket_histogram": {str(k): v for k, v in stats.bucket_histogram().items()},
    }


def run_faulted(model, params, trace, args, clean_results):
    """Goodput under injected faults: the same trace replayed through a
    seeded ``FaultPlan``.  Goodput counts only *completed* tokens; the
    run must not crash, every request must end terminal, every completed
    stream must stay bitwise identical to the clean replay, and the KV
    allocator must be fully restored."""
    from repro.launch.scheduler import ServeScheduler
    from repro.runtime.fault_injection import FaultPlan

    faults = FaultPlan.from_spec(args.faults, seed=args.seed)

    def once():
        faults.reset()
        sched = ServeScheduler(
            model, params, capacity=args.slots, block_size=args.block_size,
            max_total_len=args.max_prompt + args.max_gen,
            deadline=args.deadline or None, faults=faults)
        t0 = time.perf_counter()
        results, stats = sched.run(trace)
        return results, stats, time.perf_counter() - t0, sched

    once()  # warm-up (poison signature adds one jit variant)
    results, stats, wall, sched = once()

    assert set(results) == {r.rid for r in trace}, "a request vanished"
    alloc = sched.kv.allocator
    assert alloc.live_blocks == 0, f"{alloc.live_blocks} KV blocks leaked"
    statuses: dict[str, int] = {}
    completed_tokens = 0
    match = True
    for r in trace:
        out = results[r.rid]
        statuses[out.status.value] = statuses.get(out.status.value, 0) + 1
        if out.status.completed:
            completed_tokens += len(out.tokens)
            match &= bool(np.array_equal(out.tokens,
                                         clean_results[r.rid].tokens))
    assert match, "a completed stream diverged from the clean replay"
    return {
        "spec": args.faults,
        "walltime_s": wall,
        "requests": len(trace),
        "completed": sum(1 for r in results.values() if r.status.completed),
        "completed_tokens": completed_tokens,
        "emitted_tokens": stats.tokens,  # includes replayed + truncated work
        "goodput_tokens_per_s": completed_tokens / max(wall, 1e-9),
        "throughput_tokens_per_s": stats.tokens / max(wall, 1e-9),
        "statuses": statuses,
        "preemptions": stats.preemptions,
        "replays": stats.replays,
        "faults_injected": stats.faults_injected,
        "streams_match_clean": match,
        "crashes": 0,  # reaching this line is the proof
    }


def run_fixed(model, params, trace):
    from repro.launch.scheduler import run_fixed_batch

    run_fixed_batch(model, params, trace)  # warm-up
    results, st = run_fixed_batch(model, params, trace)
    return results, {
        "walltime_s": st["walltime_s"],
        "tokens": st["useful_tokens"],
        "tokens_per_s": st["useful_tokens"] / max(st["walltime_s"], 1e-9),
        "decode_steps": st["decode_steps"],
        "row_steps": st["row_steps"],
    }


def check_invariants(trace, results, stats, sched) -> None:
    """The scheduler contract, asserted on every benchmark replay."""
    assert set(results) == {r.rid for r in trace}, "not every request finished"
    for r in trace:
        out = results[r.rid]
        assert out.tokens is not None and len(out.tokens) == r.max_new, \
            f"req{r.rid}: {0 if out.tokens is None else len(out.tokens)} " \
            f"tokens, wanted {r.max_new}"
        assert out.admitted_step <= out.finished_step
    assert stats.prefills == len(trace)
    alloc = sched.kv.allocator
    assert alloc.live_blocks == 0, f"{alloc.live_blocks} KV blocks leaked"
    assert alloc.free_blocks == sched.kv.num_blocks - 1  # all but scratch
    assert set(stats.bucket_histogram()) <= set(sched.buckets)


def decode_gemm_hbm_bytes(plan, histogram: dict[int, int]) -> int:
    """Analytic decode-GEMM HBM traffic of one serving lane: for every
    (bucket, steps) pair in the scheduler's bucket histogram, the dtype-aware
    roofline traffic of each layer's decode sub-plan at its planned
    (dataflow, block, strip) geometry — weight at 1 byte plus the f32
    per-channel scale when the verdict quantized, bf16 operands otherwise.
    This is the decode-bandwidth economics ``--quant`` exists to buy."""
    from repro.core import GemmShape, hbm_traffic_bytes

    total = 0
    for bucket, steps in histogram.items():
        for lp in plan.layers:
            gp = lp.decode[bucket]
            g = GemmShape(M=bucket, K=lp.gemm.K, N=lp.gemm.N,
                          name=f"{lp.name}@b{bucket}")
            bm, bk, bn = gp.block
            kw = (dict(a_bytes=2, b_bytes=1, scale_bytes=4)
                  if gp.qdtype in ("int8", "fp8")
                  else dict(a_bytes=2, b_bytes=2))
            cost = hbm_traffic_bytes(g, gp.dataflow, bm, bk, bn,
                                     strip=gp.strip, **kw)
            total += steps * cost.hbm_bytes
    return total


def quant_bench(args) -> None:
    """The ``--quant`` lane: one scheduler replay for tokens/walltime, then
    the decode-GEMM bandwidth economics of the accuracy-gated quant plan vs
    the bf16 plan over the replay's actual bucket histogram — written as
    ``BENCH_quant.json`` with the gate metadata the CI checker pins."""
    from repro.core import (
        QUANT_ERROR_BUDGET,
        autotune_plan,
        model_epilogues,
        model_gemms,
    )
    from repro.launch.scheduler import poisson_trace, serve_buckets

    dtypes = tuple(q for q in args.quant.split(",") if q)
    cfg, model, params = build_model(args.profile)
    trace = poisson_trace(
        args.requests, vocab=cfg.vocab_size, max_prompt=args.max_prompt,
        max_gen=args.max_gen, rate=args.rate, seed=args.seed,
        min_prompt=args.min_prompt, min_gen=args.min_gen)
    _, cont = run_continuous(model, params, trace, args)
    histogram = {int(b): n for b, n in cont["bucket_histogram"].items()}

    buckets = serve_buckets(args.slots)
    gemms = model_gemms(cfg, args.requests * args.max_prompt)
    sigs = model_epilogues(cfg)
    bf16_plan = autotune_plan(gemms, measure=False, decode_buckets=buckets,
                              epilogue=sigs)
    quant_plan = autotune_plan(gemms, measure=False, decode_buckets=buckets,
                               epilogue=sigs, quant=dtypes)
    assert quant_plan.has_quant(buckets)

    verdicts: dict[str, int] = {}
    qerrs = []
    for lp in quant_plan.layers:
        for gp in (lp, *lp.decode.values()):
            verdicts[gp.qdtype] = verdicts.get(gp.qdtype, 0) + 1
            if gp.qerror is not None:
                qerrs.append(gp.qerror)
    b_bf16 = decode_gemm_hbm_bytes(bf16_plan, histogram)
    b_quant = decode_gemm_hbm_bytes(quant_plan, histogram)
    ratio = b_quant / max(b_bf16, 1)
    print(f"quant decode GEMM HBM: {b_quant:,} B vs bf16 {b_bf16:,} B "
          f"over buckets {histogram} = {ratio:.2f}x")
    print(f"verdicts {verdicts}, max gate error "
          f"{max(qerrs) if qerrs else 0.0:.4f} "
          f"(budget {QUANT_ERROR_BUDGET})")

    if args.json:
        record = {
            "config": {
                "profile": args.profile,
                "requests": args.requests,
                "slots": args.slots,
                "prompt_len": [args.min_prompt, args.max_prompt],
                "gen_len": [args.min_gen, args.max_gen],
                "arrival_rate": args.rate,
                "seed": args.seed,
                "model": {"d_model": cfg.d_model, "d_ff": cfg.d_ff,
                          "num_layers": cfg.num_layers,
                          "vocab_size": cfg.vocab_size},
            },
            "walltime_s": cont["walltime_s"],
            "tokens_per_s": cont["tokens_per_s"],
            "bucket_histogram": cont["bucket_histogram"],
            "quant": {
                "dtypes": list(dtypes),
                "budget": QUANT_ERROR_BUDGET,
                "verdicts": verdicts,
                "max_qerror": max(qerrs) if qerrs else 0.0,
            },
            "lanes": {
                "bf16": {"tokens": cont["tokens"],
                         "decode_hbm_bytes": b_bf16},
                "quant": {"tokens": cont["tokens"],
                          "decode_hbm_bytes": b_quant},
            },
            "decode_hbm_ratio": ratio,
        }
        with open(args.json, "w") as f:
            json.dump(record, f, indent=2)
        print(f"wrote {args.json}")


def dry_run(args) -> None:
    """CI smoke: invariants + bucket-plan dispatch, zero timing gates."""
    from repro.core import (
        activate_plan,
        autotune_plan,
        model_epilogues,
        model_gemms,
    )
    from repro.core.cmu import LayerPlan
    from repro.launch.scheduler import ServeScheduler, poisson_trace, serve_buckets
    from repro.launch.serve import sequential_reference
    from repro.models import Model, get_config

    cfg = get_config("qwen3_4b", smoke=True).replace(use_pallas=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    slots = 8
    buckets = serve_buckets(slots)
    plan = autotune_plan(model_gemms(cfg, tokens=64), measure=False,
                         decode_buckets=buckets,
                         epilogue=model_epilogues(cfg))
    assert plan.has_decode(buckets)
    activate_plan(plan)

    trace = poisson_trace(8, vocab=cfg.vocab_size, max_prompt=12, max_gen=6,
                          rate=0.5, seed=args.seed)
    sched = ServeScheduler(model, params, capacity=slots,
                           block_size=args.block_size, max_total_len=12 + 6)

    # record every decode-bucket plan lookup the pallas dispatch makes while
    # the run traces its jit signatures
    lookups: list[tuple[str, int]] = []
    orig = LayerPlan.decode_plan

    def recording(self, m):
        sub = orig(self, m)
        if sub is not None:
            lookups.append((self.name, m))
        return sub

    LayerPlan.decode_plan = recording
    try:
        results, stats = sched.run(trace)
    finally:
        LayerPlan.decode_plan = orig

    check_invariants(trace, results, stats, sched)
    hit = sorted({m for _, m in lookups})
    assert lookups, "decode steps never consulted the bucket sub-plans"
    assert set(hit) <= set(buckets), (hit, buckets)
    print(f"bucket-plan dispatch: {len(lookups)} lookups across layers, "
          f"batch buckets hit {hit} (tuned {list(buckets)})")

    ref = sequential_reference(model, params, trace,
                               sched.max_blocks * sched.block_size)
    for r in trace:
        assert np.array_equal(results[r.rid].tokens, ref[r.rid]), \
            f"req{r.rid} diverges from sequential decode"
    print(f"invariants OK: {len(trace)} requests finished, allocator "
          f"restored, streams identical to per-request sequential decode")

    # the fault-degradation contract on the same trace: injected alloc
    # failures + preemptions — no crash, every request terminal, every
    # completed stream still bitwise equal to the sequential reference
    from repro.runtime.fault_injection import FaultPlan

    faults = FaultPlan(seed=args.seed, alloc_fail=0.3, preempt=0.05)
    fsched = ServeScheduler(model, params, capacity=slots,
                            block_size=args.block_size, max_total_len=12 + 6,
                            faults=faults)
    fresults, fstats = fsched.run(trace)
    assert set(fresults) == {r.rid for r in trace}, "a request vanished"
    assert fsched.kv.allocator.live_blocks == 0, "KV blocks leaked"
    assert faults.total_injected >= 1, "the fault plan never fired"
    completed = 0
    for r in trace:
        out = fresults[r.rid]
        if out.status.completed:
            completed += 1
            assert np.array_equal(out.tokens, ref[r.rid]), \
                f"req{r.rid} diverges from sequential decode under faults"
    assert completed >= 1
    print(f"fault degradation OK: {completed}/{len(trace)} completed under "
          f"{faults.describe()} (injected {fstats.faults_injected}, "
          f"preemptions {fstats.preemptions}), completed streams bitwise "
          f"identical, allocator restored")

    # the quant planning contract on the same GEMMs: the accuracy-gated
    # quant axis annotates every forward row and decode bucket, and the
    # analytic decode traffic of a quantized verdict is strictly below the
    # bf16 plan's at the same bucket
    qplan = autotune_plan(model_gemms(cfg, tokens=64), measure=False,
                          decode_buckets=buckets,
                          epilogue=model_epilogues(cfg),
                          quant=("int8", "fp8"))
    assert qplan.has_quant(buckets), "quant tuning left a verdict missing"
    quantized = sum(gp.qdtype in ("int8", "fp8")
                    for lp in qplan.layers
                    for gp in lp.decode.values())
    assert quantized >= 1, "no decode sub-plan quantized at smoke scale"
    b0 = decode_gemm_hbm_bytes(plan, {b: 1 for b in buckets})
    b1 = decode_gemm_hbm_bytes(qplan, {b: 1 for b in buckets})
    assert b1 < b0, (b1, b0)
    print(f"quant plan OK: {quantized} quantized decode verdicts, analytic "
          f"decode HBM {b1}/{b0} = {b1 / b0:.2f}x bf16")
    print("dry-run OK")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", choices=("bench", "smoke"), default="bench")
    ap.add_argument("--requests", type=int, default=40)
    ap.add_argument("--min-prompt", type=int, default=8)
    ap.add_argument("--max-prompt", type=int, default=16)
    ap.add_argument("--min-gen", type=int, default=4)
    ap.add_argument("--max-gen", type=int, default=64)
    ap.add_argument("--rate", type=float, default=2.0,
                    help="Poisson arrivals per decode step")
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the full benchmark record as JSON")
    ap.add_argument("--faults", default="",
                    help="also measure goodput under this injected fault "
                         "spec (runtime/fault_injection.py), e.g. "
                         "'alloc=0.05,nan=0.005,preempt=0.02,latency=0.02'")
    ap.add_argument("--deadline", type=int, default=0,
                    help="queue-wait TTL in decode steps for the faulted "
                         "replay (0 = none)")
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny workload, invariants + bucket-plan dispatch "
                         "+ fault-degradation contract asserted, no timing "
                         "(CI smoke)")
    ap.add_argument("--quant", nargs="?", const="int8,fp8", default="",
                    help="measure the quant serving lane instead: one "
                         "scheduler replay plus the analytic decode-GEMM "
                         "HBM economics of the accuracy-gated quant plan "
                         "vs bf16 (bare flag = 'int8,fp8')")
    args = ap.parse_args()

    if args.dry_run:
        dry_run(args)
        return
    if args.quant:
        quant_bench(args)
        return

    from repro.launch.scheduler import poisson_trace, serve_buckets

    cfg, model, params = build_model(args.profile)
    trace = poisson_trace(
        args.requests, vocab=cfg.vocab_size, max_prompt=args.max_prompt,
        max_gen=args.max_gen, rate=args.rate, seed=args.seed,
        min_prompt=args.min_prompt, min_gen=args.min_gen)
    total = sum(r.max_new for r in trace)
    gens = sorted(r.max_new for r in trace)
    print(f"trace: {args.requests} requests, {total} tokens, gen lengths "
          f"{gens[0]}..{gens[-1]} (median {gens[len(gens) // 2]}), "
          f"arrival rate {args.rate}/step")

    clean_results, cont = run_continuous(model, params, trace, args)
    print(f"continuous: {cont['tokens']} tok in {cont['walltime_s']*1e3:.0f} ms "
          f"= {cont['tokens_per_s']:,.0f} tok/s | {cont['decode_steps']} steps, "
          f"util {cont['slot_utilization']:.2f}, "
          f"buckets {cont['bucket_histogram']}")

    _, fixed = run_fixed(model, params, trace)
    print(f"fixed batch: {fixed['tokens']} tok in {fixed['walltime_s']*1e3:.0f} ms "
          f"= {fixed['tokens_per_s']:,.0f} tok/s | {fixed['row_steps']} "
          f"row-steps for {fixed['tokens']} useful")

    speedup = cont["tokens_per_s"] / max(fixed["tokens_per_s"], 1e-9)
    print(f"continuous / fixed tokens/s: {speedup:.2f}x")

    faulted = None
    if args.faults:
        faulted = run_faulted(model, params, trace, args, clean_results)
        print(f"faulted ({faulted['spec']}): "
              f"{faulted['completed']}/{faulted['requests']} completed, "
              f"goodput {faulted['goodput_tokens_per_s']:,.0f} tok/s "
              f"({faulted['goodput_tokens_per_s']/max(cont['tokens_per_s'], 1e-9):.2f}x clean) | "
              f"statuses {faulted['statuses']}, "
              f"injected {faulted['faults_injected']}")

    if args.json:
        record = {
            "config": {
                "profile": args.profile,
                "requests": args.requests,
                "slots": args.slots,
                "block_size": args.block_size,
                "prompt_len": [args.min_prompt, args.max_prompt],
                "gen_len": [args.min_gen, args.max_gen],
                "arrival_rate": args.rate,
                "seed": args.seed,
                "model": {"d_model": cfg.d_model, "d_ff": cfg.d_ff,
                          "num_layers": cfg.num_layers,
                          "num_heads": cfg.num_heads,
                          "num_kv_heads": cfg.num_kv_heads,
                          "head_dim": cfg.head_dim,
                          "vocab_size": cfg.vocab_size},
            },
            "continuous": cont,
            "fixed_batch": fixed,
            "speedup_tokens_per_s": speedup,
        }
        if faulted is not None:
            record["faulted"] = faulted
        with open(args.json, "w") as f:
            json.dump(record, f, indent=2)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
