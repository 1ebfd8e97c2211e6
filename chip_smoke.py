"""Serve qwen3_4b at its published widths on one TPU chip, end to end.

    python chip_smoke.py                # one chip: phases (a)-(d)
    python chip_smoke.py --four-chips   # one host of four chips: the mesh phase

Everything runs in this one process, through the serving driver's own
entry points (``repro.launch.serve``), with random weights drawn from a
seed:

  (a) plan    program the CMU: load the plan at ``--plan-cache`` or autotune
              one (prefill geometry plus the decode bucket) and save it;
  (b) init    create the weights on the chip, in bf16;
  (c) serve   four requests of 64-256 prompt tokens and 16 new tokens through
              the continuous-batching scheduler, every projection a flex
              kernel; ``--verify`` requires every stream to equal classic
              per-request decode token for token;
  (d) xla     the last-token prefill logits of one prompt through the flex
              kernels against XLA's own dot on the same weights: top-1 must
              agree and the error stay under ``LOGIT_REL_TOL``.

``--four-chips`` runs only the mesh phase: the same model with ``--pallas``
on a 1x4 mesh (tensor axis 4, mesh-native flex kernels), compared with a
one-chip run of the same weights in the same process.

Before any work the script requires a TPU (and compiled, not interpreted,
kernels) and exits non-zero otherwise.  Each phase prints its compile and
wall seconds and the device's peak memory.  The last line of standard
output is one JSON object naming the device; any failure exits non-zero
before it is printed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "qwen3_4b"
REQUESTS = 4
MIN_PROMPT, MAX_PROMPT = 64, 256
GEN = 16
SEED = 0

# Phase (d) bound on max|flex - xla| / max|xla| over the last-token logits.
# Both paths multiply bf16 operands and accumulate in f32, but they round
# to bf16 at different points (the flex kernels fuse bias, activation and
# residual into the f32 flush; XLA rounds between ops) and sum in different
# orders.  bf16 keeps 8 significant bits, a rounding error of at most 2^-9
# (0.2%); 36 layers of about seven rounded ops each drift apart like a
# random walk of ~250 such errors, about 0.2% * sqrt(250) = 3% of the
# hidden state.  5% leaves room over that; a wrong block or a lost partial
# sum is an error of the order of the logits themselves.
LOGIT_REL_TOL = 0.05

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class Phases:
    """Per-phase wall and compile seconds (from JAX's monitoring events)
    and the devices' peak memory after each phase."""

    def __init__(self, devices):
        import jax

        self.devices = devices
        self.compile_s = 0.0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.compile_s += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1

    def run(self, name: str, fn, *args):
        c0, t0 = self.compile_s, time.perf_counter()
        out = fn(*args)
        wall = time.perf_counter() - t0
        # the CPU backend (a rehearsal of these phases) keeps no statistics
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        print(f"phase {name}: wall {wall:.1f} s, "
              f"compile {self.compile_s - c0:.1f} s, peak HBM "
              f"{', '.join(f'{p / 1e9:.2f}' for p in peaks)} GB", flush=True)
        return out


def serve_args(plan_cache: str, mesh: str = ""):
    from repro.launch import serve

    return serve.parse_args([
        "--arch", ARCH, "--pallas", "--verify", "--requests", str(REQUESTS),
        "--prompt-len", str(MAX_PROMPT), "--gen", str(GEN),
        "--slots", str(REQUESTS), "--arrival-rate", "0", "--seed", str(SEED),
        "--plan-cache", plan_cache, "--mesh", mesh,
    ])


def requests(vocab: int):
    from repro.launch.scheduler import poisson_trace

    return poisson_trace(REQUESTS, vocab=vocab, min_prompt=MIN_PROMPT,
                         max_prompt=MAX_PROMPT, min_gen=GEN, max_gen=GEN,
                         seed=SEED)


def plan_phase(args, cfg, mesh):
    from repro.launch import serve

    source = "loaded" if os.path.exists(args.plan_cache) else "autotuned"
    plan = serve.setup_plan(args, cfg, mesh)
    decode = sum(len(lp.decode or {}) for lp in plan.layers)
    meshed = sum(lp.mesh is not None for lp in plan.layers)
    print(f"plan {source}: {args.plan_cache}, {len(plan.layers)} rows, "
          f"{decode} decode sub-plans, {meshed} mesh sub-plans, "
          f"dataflows {plan.histogram()}", flush=True)
    return plan


def last_logits(model, params, prompt, cache_len: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.steps import make_prefill_step

    prefill = jax.jit(make_prefill_step(model, cache_len))
    _, last = prefill(params, {"tokens": jnp.asarray(prompt[None])})
    return np.asarray(jax.device_get(last), np.float32)[0]


def compare_logits(what: str, got, want) -> None:
    import numpy as np

    err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    top_got, top_want = int(np.argmax(got)), int(np.argmax(want))
    print(f"{what}: max|diff|/max|ref| = {err:.3e} (bound {LOGIT_REL_TOL}), "
          f"top-1 {top_got} vs {top_want}", flush=True)
    if not np.all(np.isfinite(got)):
        raise SystemExit(f"{what}: non-finite logits")
    if top_got != top_want or not err < LOGIT_REL_TOL:
        raise SystemExit(f"{what}: logits disagree")


def one_chip(plan_cache: str, devices) -> None:
    from repro.launch import serve
    from repro.models import Model

    phases = Phases(devices)
    args = serve_args(plan_cache)
    cfg = serve.serve_config(args)
    phases.run("a (plan)", plan_phase, args, cfg, None)
    model = Model(cfg)
    params = phases.run("b (init)", serve.init_params, model)
    trace = requests(cfg.vocab_size)
    print("prompt lengths", [int(r.prompt.size) for r in trace], flush=True)
    _, stats = phases.run("c (serve)", serve.serve_trace, args, model, params,
                          trace)
    print(f"tokens produced: {stats.tokens}", flush=True)
    if stats.tokens != REQUESTS * GEN:
        raise SystemExit(f"expected {REQUESTS * GEN} tokens, got {stats.tokens}")

    def xla_phase():
        prompt = trace[0].prompt
        flex = last_logits(model, params, prompt, MAX_PROMPT)
        xla = last_logits(Model(cfg.replace(use_pallas=False)), params,
                          prompt, MAX_PROMPT)
        compare_logits("flex vs xla prefill logits", flex, xla)

    phases.run("d (flex vs xla)", xla_phase)
    print(f"compile cache: {phases.cache['hits']} hits, "
          f"{phases.cache['misses']} misses", flush=True)


def four_chips(plan_cache: str, devices) -> None:
    import jax
    import numpy as np

    from repro.launch import serve
    from repro.models import Model
    from repro.models.sharding import use_rules

    phases = Phases(devices)
    args = serve_args(plan_cache, mesh="1x4")
    cfg = serve.serve_config(args)
    mesh = serve.parse_mesh(args.mesh)
    model = Model(cfg)
    # the mesh-native kernels take a layer only when its tokens divide the
    # mesh (``cmu.mesh_shardable``): cut each prompt to a multiple of 8
    prompts = [r.prompt[: r.prompt.size // 8 * 8]
               for r in requests(cfg.vocab_size)[:2]]
    with use_rules(mesh):
        phases.run("a (plan, 1x4 mesh)", plan_phase, args, cfg, mesh)
        sharded = phases.run("b (init, sharded)", serve.init_params, model,
                             mesh)
    per_device = {d: 0 for d in devices}
    for leaf in jax.tree.leaves(sharded):
        for shard in leaf.addressable_shards:
            per_device[shard.device] += shard.data.nbytes
    total = sum(x.nbytes for x in jax.tree.leaves(sharded))
    print(f"weights {total / 1e9:.2f} GB; held per device "
          f"{', '.join(f'{b / 1e9:.2f}' for b in per_device.values())} GB",
          flush=True)
    if max(per_device.values()) > 0.5 * total:
        raise SystemExit("the sharded weights are not spread over the mesh")

    def reference():
        params = serve.init_params(model)
        for path, a, b in zip(jax.tree_util.tree_leaves_with_path(params),
                              jax.tree.leaves(params), jax.tree.leaves(sharded)):
            if not np.array_equal(np.asarray(b), np.asarray(a)):
                raise SystemExit(f"sharded init differs at {path[0]}")
        return params, [last_logits(model, params, p, MAX_PROMPT)
                        for p in prompts]

    params, want = phases.run("c (one-chip reference)", reference)
    del params

    def mesh_prefill():
        from repro.kernels import mesh_ops

        traced = []
        sharded_linear = mesh_ops.flex_linear_sharded

        def counting(*a, **kw):
            traced.append(kw["mesh"].shape)
            return sharded_linear(*a, **kw)

        mesh_ops.flex_linear_sharded = counting
        try:
            with use_rules(mesh):
                got = [last_logits(model, sharded, p, MAX_PROMPT)
                       for p in prompts]
        finally:
            mesh_ops.flex_linear_sharded = sharded_linear
        print(f"mesh-native flex GEMMs traced: {len(traced)}", flush=True)
        if not traced:
            raise SystemExit("no projection took the mesh-native kernel path")
        return got

    got = phases.run("d (1x4 mesh prefill)", mesh_prefill)
    for i, (p, g, w) in enumerate(zip(prompts, got, want)):
        compare_logits(f"prompt {i} ({p.size} tokens) mesh vs one chip", g, w)


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 1x4 mesh phase (needs four chips)")
    ap.add_argument("--plan-cache",
                    default=str(ROOT / "build" / "chip_smoke" / f"{ARCH}_plan.json"),
                    help="CMU plan JSON: loaded if present, else autotuned "
                         "and saved")
    args = ap.parse_args(argv)

    import jax

    from repro.kernels.ops import default_interpret
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    want = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or default_interpret():
        raise SystemExit(f"no TPU: JAX sees {devices[0].platform} devices")
    if len(devices) < want:
        raise SystemExit(f"needs {want} TPU chips, JAX sees {len(devices)}")
    devices = devices[:want]
    kind = devices[0].device_kind
    print(f"device: {devices[0].platform} {kind}, {len(jax.devices())} "
          f"device(s), using {want}", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    Path(args.plan_cache).parent.mkdir(parents=True, exist_ok=True)
    if args.four_chips:
        four_chips(args.plan_cache, devices)
    else:
        one_chip(args.plan_cache, devices)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
