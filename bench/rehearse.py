"""Compile each cell's prefill and decode programs for a described TPU v5e.

    JAX_PLATFORMS=cpu python bench/rehearse.py [cell ...]

No chip is needed and nothing runs: the TPU compiler, installed here,
compiles the scheduler's jitted steps at the cell's sizes (the largest
prompt bucket of its mix, the decode step at its slot count, its pool
(``kv_positions``, or every slot's longest request) and block table) for one chip of a described ``v5e:2x2``, and prints each
program's ``memory_analysis`` beside the weight and pool bytes.  The
Pallas kernels are compiled for Mosaic (interpret mode off), under an
analytical CMU plan (the chip tunes its own; the memory is the same).
It refuses what the chip would refuse: a program over the chip's memory,
a kernel over its scoped VMEM.
"""

from __future__ import annotations

import json
import math
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def rehearse(cell_name: str, sharding) -> dict:
    import jax
    import jax.numpy as jnp

    from bench import harness
    from repro.core.plan_cache import activate_plan
    from repro.launch import serve
    from repro.launch.scheduler import _jit_steps, serve_buckets
    from repro.launch.steps import setup_plan_cache
    from repro.models import Model
    import repro.kernels.ops as ops

    ops.default_interpret = lambda: False  # compile the kernels for Mosaic
    cell = harness.load_cell(ROOT, cell_name)
    c, prog, mix = cell.config, cell.config["program"], cell.mix
    capacity = int(cell.params["slots"])
    args = serve.parse_args(["--arch", prog["arch"], *prog["flags"],
                             "--slots", str(capacity)])
    cfg = serve.serve_config(args).replace(**prog.get("overrides", {}))
    harness.published_widths_match(cfg, c)
    bs = mix.block_size
    bucket = lambda p: max(bs, 1 << (p - 1).bit_length())  # noqa: E731
    top = max(bucket(p) for p in mix.prompt_lengths())
    plan_path = ROOT / harness.STATE / "plans" / f"rehearse.{cell_name}.json"
    plan_path.parent.mkdir(parents=True, exist_ok=True)
    activate_plan(setup_plan_cache(str(plan_path), cfg, top, measure=False,
                                   decode_buckets=serve_buckets(capacity)))
    model = Model(cfg)
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=sharding)  # noqa: E731
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    nb = math.ceil(mix.max_total_len / bs)
    blocks = harness.pool_blocks(cell.params, bs) or capacity * nb + 1
    pool = sds((cfg.num_layers, blocks, bs, cfg.num_kv_heads, cfg.head_dim),
               jnp.bfloat16)
    prefill, decode = _jit_steps(model)
    i32 = jnp.int32
    out = {"weights_bytes": sum(math.prod(a.shape) * a.dtype.itemsize
                                for a in jax.tree.leaves(params)),
           "pool_bytes": 2 * math.prod(pool.shape) * 2}
    progs = {
        f"prefill@{top}": prefill.lower(params, sds((1, top), i32), sds((1,), i32),
                                        sds((1, top // bs), i32), pool, pool),
        f"decode@{capacity}": decode.lower(params, pool, pool, sds((capacity, nb), i32),
                                           sds((capacity,), i32), sds((capacity,), i32),
                                           sds((capacity,), jnp.bool_)),
    }
    for name, lowered in progs.items():
        m = lowered.compile().memory_analysis()
        out[name] = {k: getattr(m, f"{k}_size_in_bytes") for k in
                     ("argument", "output", "alias", "temp", "generated_code")}
    plan_path.unlink(missing_ok=True)
    return out


def main(argv: list[str]) -> int:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    sharding = SingleDeviceSharding(topo.devices[0])
    cells = argv or [w["name"] for w in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["workloads"]]
    for name in cells:
        print(json.dumps({"cell": name, **rehearse(name, sharding)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
