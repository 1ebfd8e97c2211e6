"""End-to-end training driver.

CPU-friendly by default (--smoke); the same flags drive a real pod:

  PYTHONPATH=src python -m repro.launch.train --arch qwen3_4b --smoke \
      --steps 200 --global-batch 8 --seq 128 --ckpt-dir /tmp/run1

Fault tolerance: the loop runs under runtime.TrainRunner — kill/restart the
process and it resumes from the last committed checkpoint; --fail-at N
injects a SimulatedNodeFailure to exercise that path in one invocation.
"""

from __future__ import annotations

import argparse
import logging
import time

import jax
import jax.numpy as jnp

from repro.data import DataConfig, TokenStream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import (
    init_train_state,
    make_train_step,
    microbatches_for,
    setup_plan_cache,
    use_quantized_opt,
)
from repro.models import Model, get_config
from repro.runtime import RunnerConfig, SimulatedNodeFailure, TrainRunner


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_4b")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--microbatches", type=int, default=0, help="0 = per-arch default")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at", type=int, default=-1, help="inject a node failure")
    ap.add_argument("--d-model", type=int, default=0, help="override width")
    ap.add_argument("--layers", type=int, default=0, help="override depth")
    ap.add_argument("--plan-cache", default="",
                    help="CMU plan JSON: reload if present, else autotune + save")
    ap.add_argument("--pallas", action="store_true",
                    help="dispatch projections — forward AND backward GEMMs "
                         "— to the fused flex kernels via the custom VJP; "
                         "the plan cache then carries per-layer fwd/dX/dW "
                         "sub-plans")
    ap.add_argument("--mesh", default="",
                    help="'DxM' data x model mesh (e.g. 2x4): train "
                         "multi-device — with --pallas the projections run "
                         "the shard_map-composed mesh-native kernel path")
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    if args.d_model:
        cfg = cfg.replace(d_model=args.d_model)
    if args.layers:
        cfg = cfg.replace(num_layers=args.layers)
    if args.pallas:
        cfg = cfg.replace(use_pallas=True)

    import contextlib

    from repro.launch.serve import parse_mesh

    mesh = parse_mesh(args.mesh)
    if mesh is not None:
        from repro.models.sharding import use_rules

        rules_ctx = use_rules(mesh)
    else:
        rules_ctx = contextlib.nullcontext()
    with rules_ctx:
        _train(args, cfg, mesh)


def _train(args, cfg, mesh) -> None:
    mb = args.microbatches or microbatches_for(args.arch)
    mb = mb if args.global_batch % max(mb, 1) == 0 else 1
    # training plans group each layer's three GEMMs (fwd + dX + dW) so the
    # backward pass reconfigures per layer too; under grad accumulation each
    # GEMM runs per microbatch, so that is the geometry to tune for
    setup_plan_cache(args.plan_cache, cfg,
                     args.global_batch // max(mb, 1) * args.seq,
                     train=args.pallas, mesh=mesh)
    model = Model(cfg)
    total, active = cfg.param_count()
    print(f"arch={cfg.name} params={total/1e6:.1f}M (active {active/1e6:.1f}M)")

    stream = TokenStream(
        DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.global_batch)
    )
    jit_step = jax.jit(
        make_train_step(
            model, peak_lr=args.lr, warmup=args.warmup,
            total_steps=args.steps, microbatches=mb,
        ),
        donate_argnums=(0, 1),
    )

    def init():
        params, opt = init_train_state(
            model, jax.random.PRNGKey(0), quantize_opt=use_quantized_opt(args.arch)
        )
        if mesh is not None:
            from repro.models.sharding import param_shardings

            params = jax.device_put(params, param_shardings(params))
        return {"params": params, "opt": opt}

    times = []

    def step_fn(state, i):
        t0 = time.time()
        batch = {k: jnp.asarray(v) for k, v in stream.batch(i).items()}
        params, opt, metrics = jit_step(state["params"], state["opt"], batch)
        loss = float(metrics["loss"])
        times.append(time.time() - t0)
        if i % 10 == 0 or i == args.steps - 1:
            tps = args.global_batch * args.seq / max(times[-1], 1e-9)
            print(f"step {i:5d} loss {loss:.4f} lr {float(metrics['lr']):.2e} "
                  f"{times[-1]*1e3:.0f} ms/step {tps:,.0f} tok/s")
        return {"params": params, "opt": opt}, {"loss": loss}

    hook = None
    if args.fail_at >= 0:
        fired = []

        def hook(step):  # noqa: ANN001
            if step == args.fail_at and not fired:
                fired.append(1)
                raise SimulatedNodeFailure(f"injected at step {step}")

    runner = TrainRunner(
        step_fn, init,
        RunnerConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                     max_steps=args.steps),
        failure_hook=hook,
    )
    state, step = runner.run()
    losses = [m["loss"] for m in runner.metrics_log]
    trajectory = (f"loss {losses[0]:.4f} -> {losses[-1]:.4f}" if losses
                  else "no new steps (checkpoint already at --steps)")
    print(f"done: {step} steps, restarts={runner.restarts}, {trajectory}")


if __name__ == "__main__":
    main()
