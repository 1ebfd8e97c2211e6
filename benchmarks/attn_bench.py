"""Attention schedule-family benchmark: prefill sweeps + per-bucket decode.

Times every prefill schedule variant (q-stationary vs kv-stationary at a
fixed block geometry) and both decode-attention kinds (in-place Pallas
paged kernel vs the pure-jnp gather baseline) per serving bucket, and
reports walltime next to the analytical cost model's HBM traffic and VMEM
residency for each — the numbers the CMU ranks schedules by.  The bench
shape is long-context GQA prefill (group 2), the regime where the
kv-stationary sweep's K/V-resident HBM win shows up.

  PYTHONPATH=src python benchmarks/attn_bench.py
  PYTHONPATH=src python benchmarks/attn_bench.py --json benchmarks/BENCH_attn.json
  PYTHONPATH=src python benchmarks/attn_bench.py --dry-run   # CI smoke

``--dry-run`` is the CI lane's functional smoke: tiny shape, no timing
gates — it asserts the family's correctness invariants instead (both
sweep orders bitwise-identical, the paged decode kernel matching its
gather oracle, and the analytical ordering the schema check pins).
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

import jax
import jax.numpy as jnp


def bench_shape(dry: bool):
    from repro.core import AttnShape

    if dry:
        return AttnShape(seq=64, kv=64, heads=4, kv_heads=2, head_dim=16)
    return AttnShape(seq=512, kv=512, heads=4, kv_heads=2, head_dim=32)


def _time(run, iters: int, warmup: int = 1) -> float:
    for _ in range(warmup):
        run().block_until_ready()
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        run().block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_prefill(shape, iters: int, interpret: bool) -> dict:
    """Both sweep orders at the same (bq, bk): same bits, different
    traffic — walltime + the cost model's HBM/VMEM per variant."""
    from repro.core import attn_traffic_bytes
    from repro.kernels.flash_attention import mha_flash

    bq = bk = min(128, max(-(-shape.rows // 8) * 8, 8))
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (1, shape.seq, shape.heads, shape.head_dim),
                          jnp.float32)
    k = jax.random.normal(kk, (1, shape.kv, shape.kv_heads, shape.head_dim),
                          jnp.float32)
    v = jax.random.normal(kv_, (1, shape.kv, shape.kv_heads, shape.head_dim),
                          jnp.float32)
    out = {}
    bits = {}
    for sweep in ("q", "kv"):
        run = lambda s=sweep: mha_flash(q, k, v, causal=True, block_q=bq,
                                        block_k=bk, sweep=s,
                                        interpret=interpret)
        cost = attn_traffic_bytes(shape, sweep, bq, bk,
                                  in_bytes=2, out_bytes=2)
        bits[sweep] = np.asarray(run()).tobytes()
        out[sweep] = {
            "block": [bq, bk],
            "walltime_s": _time(run, iters),
            "hbm_bytes": cost.hbm_bytes,
            "vmem_bytes": cost.vmem_bytes,
        }
    assert bits["q"] == bits["kv"], \
        "sweep orders diverged bitwise — the schedule family is broken"
    return out


def bench_decode(shape, buckets, iters: int, interpret: bool) -> dict:
    """Per-bucket decode step: the Pallas paged kernel vs the jnp gather,
    over a proxy paged cache (same construction the CMU's timer uses)."""
    from repro.core import attn_decode_traffic_bytes
    from repro.kernels.flash_attention import (
        paged_attention,
        paged_attention_reference,
    )

    bs = 16
    cache_len = max(min(shape.kv, 64), bs)
    nb = -(-cache_len // bs)
    out = {}
    for b in buckets:
        kq, kp = jax.random.split(jax.random.PRNGKey(b))
        q = jax.random.normal(kq, (b, shape.heads, shape.head_dim),
                              jnp.float32)
        pools = jax.random.normal(
            kp, (2, b * nb + 1, bs, shape.kv_heads * shape.head_dim),
            jnp.float32)
        table = 1 + jnp.arange(b * nb, dtype=jnp.int32).reshape(b, nb)
        positions = jnp.full((b,), cache_len - 1, jnp.int32)
        args = (q, pools[0], pools[1], table, positions)
        paged = jax.jit(lambda a, k_, v_, t, p: paged_attention(
            a, k_, v_, t, p, interpret=interpret))
        gather = jax.jit(paged_attention_reference)
        np.testing.assert_allclose(np.asarray(paged(*args)),
                                   np.asarray(gather(*args)),
                                   atol=2e-5, rtol=2e-5)
        row = {}
        for kind, run in (("paged", paged), ("gather", gather)):
            cost = attn_decode_traffic_bytes(shape, kind, b, block_size=bs,
                                             in_bytes=2, out_bytes=2)
            row[kind] = {
                "walltime_s": _time(lambda r=run: r(*args), iters),
                "hbm_bytes": cost.hbm_bytes,
                "vmem_bytes": cost.vmem_bytes,
            }
        out[str(b)] = row
    return out


def planned_schedule(shape, buckets, iters: int, interpret: bool) -> dict:
    """What the CMU would actually pick for this shape (measured)."""
    from repro.core import cmu

    ap = cmu._tune_attention(
        shape, tuple(buckets), vmem_limit=cmu.VMEM_BUDGET_BYTES, top_k=3,
        measure=True, iters=iters, interpret=interpret)
    return {
        "sweep": ap.sweep,
        "block": list(ap.block),
        "source": ap.source,
        "decode_kinds": {str(b): sub.sweep for b, sub in
                         sorted(ap.decode.items())},
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="", help="write the record here")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--dry-run", action="store_true",
                    help="CI smoke: tiny shape, correctness asserts only")
    args = ap.parse_args()

    from repro.core import DECODE_BUCKETS
    from repro.kernels.ops import default_interpret

    interpret = default_interpret()
    shape = bench_shape(args.dry_run)
    buckets = DECODE_BUCKETS if not args.dry_run else (8, 16)
    iters = 1 if args.dry_run else args.iters

    rec = {
        "config": {
            "seq": shape.seq, "kv": shape.kv, "heads": shape.heads,
            "kv_heads": shape.kv_heads, "head_dim": shape.head_dim,
            "group": shape.group, "iters": iters, "interpret": interpret,
            "buckets": list(buckets),
        },
        "prefill": bench_prefill(shape, iters, interpret),
        "decode": bench_decode(shape, buckets, iters, interpret),
        "planned": planned_schedule(shape, buckets, iters, interpret),
    }

    pf = rec["prefill"]
    print(f"prefill {shape.seq}x{shape.kv} g={shape.group} "
          f"(bq,bk)={tuple(pf['q']['block'])}")
    for sweep in ("q", "kv"):
        r = pf[sweep]
        print(f"  {sweep:>2}-stationary: {r['walltime_s'] * 1e3:8.2f} ms   "
              f"hbm {r['hbm_bytes'] / 1e6:8.2f} MB   "
              f"vmem {r['vmem_bytes'] / 1024:6.1f} KiB")
    print("decode (per bucket):")
    for b, row in rec["decode"].items():
        line = f"  b={b:>3}:"
        for kind in ("paged", "gather"):
            r = row[kind]
            line += (f"  {kind} {r['walltime_s'] * 1e3:7.2f} ms "
                     f"({r['hbm_bytes'] / 1e3:7.1f} KB hbm)")
        print(line)
    p = rec["planned"]
    print(f"planned: {p['sweep']}-stationary {tuple(p['block'])} "
          f"[{p['source']}], decode kinds {p['decode_kinds']}")

    if args.dry_run:
        # no timing gates on CI hardware — the correctness asserts above
        # (bitwise sweep agreement, paged-vs-gather closeness) already ran
        print("dry-run OK")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rec, f, indent=1, sort_keys=True)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
