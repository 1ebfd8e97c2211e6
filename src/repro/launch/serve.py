"""Serving driver: continuous batching over the paged KV cache.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3_4b --smoke \
      --requests 10 --prompt-len 12 --gen 6 --arrival-rate 0.5 --verify

Default mode is the continuous-batching scheduler (``launch.scheduler``):
Poisson-staggered requests are admitted into free slots as they arrive,
finished ones evicted per step, decode batches quantized to the tuned CMU
batch buckets.  ``--verify`` replays every request through classic
per-request ``prefill``/``decode_step`` serving and asserts the token
streams are identical.  ``--fixed-batch`` runs the old fixed-batch loop
instead (the benchmark baseline).

Multi-device (the mesh-native flex kernel path; on CPU give jax virtual
devices first):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.serve --arch qwen3_4b --smoke --pallas \
      --mesh 2x4 --requests 8 --prompt-len 12 --gen 4
"""

from __future__ import annotations

import argparse
import contextlib
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.scheduler import (
    RequestStatus,
    ServeScheduler,
    poisson_trace,
    run_fixed_batch,
    serve_buckets,
)
from repro.launch.steps import make_decode_step, make_prefill_step, setup_plan_cache
from repro.models import Model, get_config
from repro.runtime.fault_injection import FaultPlan


def parse_mesh(spec: str):
    """'DxM' -> a ('data', 'model') mesh, e.g. '2x4'; '' -> None."""
    if not spec:
        return None
    from repro.launch.mesh import make_mesh

    d, m = (int(v) for v in spec.lower().split("x"))
    return make_mesh((d, m), ("data", "model"))


def sequential_reference(model, params, requests, cache_len: int,
                         width: int = 1):
    """Classic per-request serving: exact-length prefill, then decode of
    that one request over a dense cache.  The correctness oracle for the
    continuous-batching path.

    ``width`` runs the decode step at that batch width, the request in
    every row.  XLA on a TPU compiles a step differently for each batch
    width, and the rows of a batch-4 step need not equal a batch-1 step
    bitwise (observed on a v5e at qwen3_4b's widths; every op alone is
    batch-invariant there, the fused step is not).  Comparing a scheduler
    that decoded at one bucket width against the reference at that width
    keeps the check bitwise."""
    prefill = jax.jit(make_prefill_step(model, cache_len))
    decode = jax.jit(make_decode_step(model))
    widen = lambda a: jnp.repeat(a, width, axis=1) if a.ndim > 1 else a
    out = {}
    for r in requests:
        cache, last = prefill(params, {"tokens": jnp.asarray(r.prompt[None])})
        cache = jax.tree.map(widen, cache)
        tok = jnp.repeat(jnp.argmax(last, -1).astype(jnp.int32), width)
        toks = [tok]
        for _ in range(r.max_new - 1):
            logits, cache = decode(params, cache, tok)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            toks.append(tok)
        out[r.rid] = np.asarray([int(t[0]) for t in jax.device_get(toks)], np.int32)
    return out


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_4b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=24,
                    help="max prompt length (trace mixes [4, max])")
    ap.add_argument("--gen", type=int, default=16,
                    help="max generated tokens (trace mixes [2, max])")
    ap.add_argument("--arrival-rate", type=float, default=0.5,
                    help="Poisson arrivals per decode step; 0 = all at once")
    ap.add_argument("--slots", type=int, default=8,
                    help="slot-table capacity (= max decode batch bucket)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV cache block size in tokens")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--verify", action="store_true",
                    help="assert token streams == classic per-request decode "
                         "(under --faults: every *completed* stream must "
                         "still match, and every request must end in a "
                         "terminal status)")
    ap.add_argument("--deadline", type=int, default=0,
                    help="queue-wait TTL in decode steps: a request still "
                         "waiting past it times out (0 = no deadline)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bound on the waiting queue; the newest arrival is "
                         "load-shed when it would overflow (0 = unbounded)")
    ap.add_argument("--faults", default="",
                    help="deterministic fault injection, e.g. "
                         "'alloc=0.1,nan=0.02,preempt=0.05,latency=0.01"
                         "[,seed=N]' (see runtime/fault_injection.py)")
    ap.add_argument("--fixed-batch", action="store_true",
                    help="run the legacy fixed-batch loop instead")
    ap.add_argument("--cache-len", type=int, default=128,
                    help="dense cache length for --fixed-batch")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (--fixed-batch only; the "
                         "scheduler is greedy for determinism)")
    ap.add_argument("--plan-cache", default="",
                    help="CMU plan JSON: reload if present, else autotune + "
                         "save (bucketed decode sub-plans included)")
    ap.add_argument("--pallas", action="store_true",
                    help="dispatch projections to the fused flex kernels")
    ap.add_argument("--quant", nargs="?", const="int8,fp8", default="",
                    help="tune weight-quantized decode/prefill GEMMs: a "
                         "comma list of dtypes from {int8, fp8} (bare flag "
                         "= 'int8,fp8').  Each layer is accuracy-gated and "
                         "either dispatches the quantized kernel with its "
                         "fused dequant epilogue or records a bf16 "
                         "fallback in the plan; requires --pallas to "
                         "change dispatch")
    ap.add_argument("--quant-budget", type=float, default=None,
                    help="accuracy gate bound: max relative RMS calibration "
                         "error a quantized layer may add (default "
                         "cmu.QUANT_ERROR_BUDGET)")
    ap.add_argument("--attn-pallas", action="store_true",
                    help="dispatch attention to the planned flex flash/"
                         "paged kernel family (prefill flash + per-bucket "
                         "Pallas paged decode)")
    ap.add_argument("--ssm-pallas", action="store_true",
                    help="dispatch the ssm/hybrid mixer scan to the planned "
                         "flex chunked-scan kernel family (prefill chunked "
                         "scan + per-bucket fused decode step); no-op on "
                         "attention-only archs")
    ap.add_argument("--mesh", default="",
                    help="'DxM' data x model mesh (e.g. 2x4): serve "
                         "multi-device — projections run the shard_map-"
                         "composed mesh-native kernel path when --pallas")
    return ap.parse_args(argv)


def serve_config(args: argparse.Namespace):
    """The model config a serving run uses.  Weights are held in bf16 from
    creation on: serving never updates them, so an f32 master copy is
    memory (17.6 GB for qwen3_4b, more than a v5e holds) and an f32->bf16
    cast in every step."""
    cfg = get_config(args.arch, smoke=args.smoke).replace(
        param_dtype="bfloat16")
    if args.pallas:
        cfg = cfg.replace(use_pallas=True)
    if args.attn_pallas:
        cfg = cfg.replace(attn_pallas=True)
    if args.ssm_pallas:
        cfg = cfg.replace(ssm_pallas=True)
    return cfg


def main(argv: list[str] | None = None) -> None:
    args = parse_args(argv)
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
    enable_compile_cache()
    cfg = serve_config(args)
    mesh = parse_mesh(args.mesh)
    if mesh is not None:
        from repro.models.sharding import use_rules

        rules_ctx = use_rules(mesh)
    else:
        rules_ctx = contextlib.nullcontext()
    with rules_ctx:
        _serve(args, cfg, mesh)


def setup_plan(args: argparse.Namespace, cfg, mesh):
    """Program the CMU for this run (``setup_plan_cache``): load the plan
    at ``--plan-cache``, or autotune one for the run's prefill geometry and
    decode buckets and save it there."""
    buckets = None if args.fixed_batch else serve_buckets(args.slots)
    quant = tuple(q for q in args.quant.split(",") if q) or None
    return setup_plan_cache(args.plan_cache, cfg,
                            args.requests * args.prompt_len, mesh=mesh,
                            decode_buckets=buckets, quant=quant,
                            quant_budget=args.quant_budget)


def init_params(model: Model, mesh=None, seed: int = 0):
    """Random weights from ``seed``, made on the device by one jitted
    program: each weight is drawn and cast to ``param_dtype`` there, so no
    f32 copy of a bf16 model ever exists, and under ``mesh`` (inside its
    ``use_rules`` context) each leaf is written straight into its
    sharding rather than placed on one device first."""
    key = jax.random.PRNGKey(seed)
    shardings = None
    if mesh is not None:
        from repro.models.sharding import param_shardings

        shardings = param_shardings(jax.eval_shape(model.init, key))
    return jax.jit(model.init, out_shardings=shardings)(key)


def _serve(args, cfg, mesh) -> None:
    setup_plan(args, cfg, mesh)
    model = Model(cfg)
    params = init_params(model, mesh, args.seed)

    if args.fixed_batch:
        _serve_fixed(args, cfg, model, params)
        return

    trace = poisson_trace(
        args.requests, vocab=cfg.vocab_size, max_prompt=args.prompt_len,
        max_gen=args.gen, rate=args.arrival_rate, seed=args.seed)
    serve_trace(args, model, params, trace)


def serve_trace(args, model: Model, params, trace):
    """Serve ``trace`` through the continuous-batching scheduler and print
    what it did; with ``--verify``, raise ``SystemExit`` unless every
    completed stream equals classic per-request decode.  Returns the
    scheduler's ``(results, stats)``."""
    faults = (FaultPlan.from_spec(args.faults, seed=args.seed)
              if args.faults else None)
    sched = ServeScheduler(
        model, params, capacity=args.slots, block_size=args.block_size,
        max_total_len=args.prompt_len + args.gen,
        deadline=args.deadline or None, max_queue=args.max_queue or None,
        faults=faults)
    t0 = time.perf_counter()
    results, stats = sched.run(trace)
    wall = time.perf_counter() - t0

    print(f"continuous batching: {len(trace)} reqs, {stats.tokens} tokens "
          f"in {wall*1e3:.0f} ms ({stats.tokens/max(wall, 1e-9):,.0f} tok/s)")
    print(f"  {stats.steps} decode steps, {stats.prefills} prefills, "
          f"slot utilization {stats.slot_utilization:.2f}, "
          f"bucket histogram {stats.bucket_histogram()}")
    host = stats.host_seconds()
    print("  host ms: " + ", ".join(
        f"{label} {host.get(name, 0.0) * 1e3:.1f}" for label, name in (
            ("admit", "serve.admit"), ("decode dispatch", "serve.decode"),
            ("sync wait", "serve.sync"), ("drain", "serve.drain"),
            ("bookkeeping", "bookkeeping"))))
    if faults is not None or stats.rejections or stats.timeouts:
        statuses: dict[str, int] = {}
        for res in results.values():
            statuses[res.status.value] = statuses.get(res.status.value, 0) + 1
        print(f"  statuses {statuses} | preemptions {stats.preemptions}, "
              f"replays {stats.replays}, injected {stats.faults_injected}")
    for r in trace[:3]:
        res = results[r.rid]
        toks = res.tokens[:12].tolist() if res.tokens is not None else None
        print(f"  req{r.rid} [{res.status.value}]: {toks}")

    if args.verify:
        cache_len = sched.max_blocks * sched.block_size
        completed = [r for r in trace if results[r.rid].status.completed]
        # one decode width throughout: compare at that width (see
        # ``sequential_reference``); several: the batch-1 reference
        widths = set(stats.bucket_per_step)
        width = widths.pop() if len(widths) == 1 else 1
        ref = sequential_reference(model, params, completed, cache_len, width)
        bad = [r.rid for r in completed
               if not np.array_equal(results[r.rid].tokens, ref[r.rid])]
        if bad:
            for rid in bad[:3]:
                print(f"  MISMATCH req{rid}: scheduler "
                      f"{results[rid].tokens.tolist()} != sequential "
                      f"{ref[rid].tolist()}")
            raise SystemExit(
                f"verify FAILED: {len(bad)}/{len(completed)} completed "
                "streams diverge from per-request sequential decode")
        if faults is not None:
            terminal = all(isinstance(res.status, RequestStatus)
                           for res in results.values())
            assert terminal and len(results) == len(trace)
            print(f"verify: {len(completed)}/{len(trace)} completed under "
                  f"{faults.describe()}; every completed stream identical "
                  "to per-request sequential decode, every request in a "
                  "terminal status")
        else:
            print(f"verify: {len(completed)}/{len(trace)} token streams "
                  "identical to per-request sequential decode")
    return results, stats


def _serve_fixed(args, cfg, model, params) -> None:
    rng = np.random.default_rng(args.seed)
    reqs = []
    from repro.launch.scheduler import Request

    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size, size=args.prompt_len).astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt, max_new=args.gen))
    if args.temperature > 0:
        # keep the legacy sampling path exercisable
        print("note: --temperature samples only in the legacy loop; results "
              "are not comparable across runs")
    results, st = run_fixed_batch(model, params, reqs, cache_len=args.cache_len)
    print(f"fixed batch: {args.requests}x{args.gen} tokens in "
          f"{st['walltime_s']*1e3:.0f} ms "
          f"({st['useful_tokens']/max(st['walltime_s'], 1e-9):,.0f} tok/s, "
          f"{st['row_steps']} row-steps for {st['useful_tokens']} useful)")
    for i in range(min(3, args.requests)):
        print(f"  req{i}: {results[i][:12].tolist()}")


if __name__ == "__main__":
    main()
