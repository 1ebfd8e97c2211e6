"""Compile-only rehearsal of the main path's kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology and refuses what the chip would
refuse — VMEM over the scoped limit, unaligned tiles, a kernel that cannot
be lowered.  Interpret mode sees none of this.  Every case compiles one
kernel or step at qwen3_4b's published widths (d_model 2560, d_ff 9728,
32/8 GQA heads of 128, vocabulary 152064 padded), or where it says so at
minicpm_2b's (d_model 2304, 36 MHA heads of 64), and nothing runs.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import autotune_plan, model_gemms
from repro.core.cmu import _ranked_candidates
from repro.core.dataflow import (
    VMEM_BUDGET_BYTES,
    Dataflow,
    GemmShape,
    hbm_traffic_bytes,
)
from repro.kernels import flex_linear, mha_flash, paged_attention
from repro.models import Model, get_config

CFG = get_config("qwen3_4b")
D, F, V = CFG.d_model, CFG.d_ff, CFG.padded_vocab
H, HKV, HD = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim
PREFILL = 512  # prefill tokens
BUCKET = 8     # decode rows


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import compilation_cache, topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; return its HLO text."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # the Pallas kernel is in the program
    return text


def _linear(one_chip, M, K, N, df, block, strip=1, *, qdtype=None,
            activation=None, residual=False):
    def fn(x, w, r):
        return flex_linear(x, w, activation=activation,
                           residual=r if residual else None, dataflow=df,
                           block=block, strip=strip, qdtype=qdtype,
                           out_dtype=jnp.bfloat16, interpret=False)

    bf = jnp.bfloat16
    _compile(fn, one_chip, ((M, K), bf), ((K, N), bf), ((M, N), bf))


# (layer, M, K, N, dataflow, block, strip, epilogue): streamed schedules run
# on the chip only with bk = K (``dataflow.revisits_output``)
GEMMS = [
    ("attn.wq", PREFILL, D, H * HD, Dataflow.OS, (256, 256, 256), 1, {}),
    ("mlp.w1", PREFILL, D, F, Dataflow.IS, (512, D, 256), 1,
     {"activation": "silu"}),
    ("mlp.w2", PREFILL, F, D, Dataflow.WS, (128, F, 128), 1,
     {"residual": True}),
    ("mlp.w1", PREFILL, D, F, Dataflow.IS, (512, 128, 128), 19,
     {"activation": "silu"}),
    ("mlp.w2", PREFILL, F, D, Dataflow.WS, (128, 512, 512), 4,
     {"residual": True}),
    ("lm_head", PREFILL, D, V, Dataflow.IS, (512, D, 128), 1, {}),
    ("mlp.w1@b8", BUCKET, D, F, Dataflow.OS, (BUCKET, D, 512), 1,
     {"activation": "silu"}),
    ("mlp.w1@b8 int8", BUCKET, D, F, Dataflow.IS, (BUCKET, 512, 512), 19,
     {"activation": "silu", "qdtype": "int8"}),
]


@pytest.mark.parametrize(
    "name,M,K,N,df,block,strip,epilogue", GEMMS,
    ids=[f"{g[0]}-{g[4].name}-s{g[6]}" for g in GEMMS])
def test_flex_gemm_compiles(one_chip, name, M, K, N, df, block, strip,
                            epilogue):
    _linear(one_chip, M, K, N, df, block, strip, **epilogue)


@pytest.mark.parametrize("qdtype", ["int8", "fp8"])
def test_largest_admitted_quant_block_compiles(one_chip, qdtype):
    """The VMEM model is an upper bound at its edge: the quantized decode
    schedule with the largest modelled working set the budget admits
    compiles (a 1-byte weight block is widened to f32 in VMEM, through a
    second 32-bit buffer for fp8)."""
    gemm = GemmShape(BUCKET, D, F, name="mlp.w1@b8")
    ranked = [c for c in _ranked_candidates(gemm, VMEM_BUDGET_BYTES,
                                            quant=(qdtype,), on_chip=True)
              if c[4] == qdtype]
    _, df, block, strip, _ = max(ranked, key=lambda c: hbm_traffic_bytes(
        gemm, c[1], *c[2], strip=c[3], a_bytes=2, b_bytes=1,
        scale_bytes=4).vmem_bytes)
    _linear(one_chip, BUCKET, D, F, df, block, strip, qdtype=qdtype,
            activation="silu")


@pytest.mark.parametrize("sweep", ["q", "kv"])
def test_flash_prefill_compiles(one_chip, sweep):
    bf = jnp.bfloat16
    _compile(lambda q, k, v: mha_flash(q, k, v, causal=True, sweep=sweep,
                                       block_q=128, block_k=128,
                                       interpret=False),
             one_chip, ((1, PREFILL, H, HD), bf), ((1, PREFILL, HKV, HD), bf),
             ((1, PREFILL, HKV, HD), bf))


def _paged_kernel(one_chip, heads, kv_heads, hd):
    bs, nb, blocks = 16, 17, 4 * 17 + 1
    bf, i32 = jnp.bfloat16, jnp.int32
    _compile(lambda q, pk, pv, t, p: paged_attention(q, pk, pv, t, p,
                                                     interpret=False),
             one_chip, ((BUCKET, heads, hd), bf), ((blocks, bs, kv_heads * hd), bf),
             ((blocks, bs, kv_heads * hd), bf), ((BUCKET, nb), i32),
             ((BUCKET,), i32))


def test_paged_decode_compiles(one_chip):
    _paged_kernel(one_chip, H, HKV, HD)


def test_paged_decode_compiles_at_heads_of_64(one_chip):
    """minicpm_2b's 36 MHA heads of 64: the kernel reads the pools' rows
    whole, so no block is split into heads narrower than a lane tile."""
    _paged_kernel(one_chip, 36, 36, 64)


def test_cmu_analytical_plan_compiles(one_chip):
    """Every row the CMU plans for qwen3_4b on the chip — prefill and the
    decode bucket — compiles under the VMEM budget its model admitted."""
    plan = autotune_plan(model_gemms(CFG, PREFILL), measure=False,
                         interpret=False, decode_buckets=(BUCKET,))
    for lp in plan.layers:
        for M, gp in [(PREFILL, lp), (BUCKET, lp.decode[BUCKET])]:
            _linear(one_chip, M, lp.gemm.K, lp.gemm.N, gp.dataflow,
                    gp.block, gp.strip)


# ops that move a pool's blocks as a whole; an op with one of these opcodes
# whose result holds more than one KV block copies (part of) a pool
POOL_MOVES = ("copy", "copy-start", "copy-done", "dynamic-slice",
              "dynamic-update-slice", "slice", "slice-start", "slice-done")
INSTRUCTION = re.compile(r"\s*(?:ROOT )?%(\S+) = (.*?) ([\w\-]+)\(")


def _paged_decode_program(one_chip, monkeypatch, cfg, slots, blocks,
                          per_slot=96, bs=16):
    """The scheduler's decode program for ``cfg`` at ``slots`` slots, each
    with a table of ``per_slot`` blocks, over a pool of ``blocks`` blocks
    of ``bs`` positions, compiled for the described chip."""
    import repro.kernels.ops as ops
    from repro.launch.scheduler import _jit_steps

    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    model = Model(cfg)
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pool = sds((cfg.num_layers, blocks, bs, cfg.kv_dim), jnp.bfloat16)
    i32 = jnp.int32
    _, decode = _jit_steps(model)
    compiled = decode.lower(params, pool, pool, sds((slots, per_slot), i32),
                            sds((slots,), i32), sds((slots,), i32),
                            sds((slots,), jnp.bool_)).compile()
    return compiled, pool


def _results(text, *, buffers=False):
    """(op, instruction name, dtype, dims) of every array an instruction
    yields; with ``buffers``, only those of the program's own computations
    (an instruction inside a fusion's computation is no buffer)."""
    out, fused = [], False
    for line in text.splitlines():
        if line and not line[0].isspace():
            fused = line.lstrip("%").startswith(("fused", "wrapped"))
            continue
        m = INSTRUCTION.match(line)
        if m is None or (fused and buffers):
            continue
        name, result, op = m.groups()
        for dt, dims in re.findall(r"(\w+)\[([\d,]+)\]", result):
            out.append((op, name, dt, tuple(int(x) for x in dims.split(","))))
    return out


def _pool_moves(text, row):
    """The copy, slice and update ops whose result holds more than one
    block of ``row``, and the shapes scattered into."""
    moves, scatters = [], set()
    for op, name, _, d in _results(text):
        if d[-len(row):] != row:
            continue
        if op in POOL_MOVES and math.prod(d[:-len(row)]) > 1:
            moves.append(f"{op} {name} {d}")
        if op == "scatter":
            scatters.add(d)
    return moves, scatters


@pytest.mark.parametrize("attn", ["xla", "pallas"])
def test_paged_decode_step_copies_no_pool(one_chip, monkeypatch, attn):
    """The scheduler's decode program, for two layers of qwen3_4b at the
    decode cell's 48 slots and a pool of the cell's size (2,819 blocks, a
    count no other op has, and too large for the compiler to keep in VMEM),
    appends into and reads the stacked pools in place: no copy, slice or
    update op of its HLO yields a layer's pool, a part of one, or the
    stacked pools, and the pools come out aliased to the donated inputs."""
    cfg = CFG.replace(num_layers=2, use_pallas=True, tie_embeddings=True,
                      attn_pallas=attn == "pallas")
    compiled, pool = _paged_decode_program(one_chip, monkeypatch, cfg, 48, 2819)
    row = (16, HKV * HD)
    moves, scatters = _pool_moves(compiled.as_text(), row)
    assert not moves, moves
    # the new token's row lands in the flattened stacked pools themselves
    assert (cfg.num_layers * 2819, *row) in scatters, scatters
    pool_bytes = math.prod(pool.shape) * 2
    assert compiled.memory_analysis().alias_size_in_bytes >= 2 * pool_bytes


def test_mha_decode_step_holds_no_f32_kv(one_chip, monkeypatch):
    """Two layers of minicpm_2b (36 MHA heads of 64) at the decode cell's
    24 slots and pool (1,409 blocks): heads of 64 are never split out of
    the pools' rows nor widened to f32, so no buffer of the program is an
    f32 tensor as large as the gathered K (or V) view, every buffer of
    pool rows is unpadded ``(16, 2304)``, and no op copies a pool."""
    from repro.models import get_config

    cfg = get_config("minicpm_2b").replace(num_layers=2, use_pallas=True,
                                           param_dtype="bfloat16")
    slots, per_slot, bs = 24, 96, 16
    compiled, pool = _paged_decode_program(one_chip, monkeypatch, cfg, slots,
                                           1409, per_slot, bs)
    text = compiled.as_text()
    gathered = slots * per_slot * bs * cfg.kv_dim
    wide = [(op, name, d) for op, name, dt, d in _results(text, buffers=True)
            if dt == "f32" and math.prod(d) >= gathered]
    assert not wide, wide
    row = (bs, cfg.kv_dim)
    moves, scatters = _pool_moves(text, row)
    assert not moves, moves
    assert (cfg.num_layers * 1409, *row) in scatters, scatters
    assert re.search(r"bf16\[2304,16,2304\]\{2,1,0:T\(8,128\)\(2,1\)\}[^\n]*"
                     r"attn\.kv_gather", text)
