"""Flex attention schedule family: the property sweep that keeps a
multi-variant kernel family honest.

Pins four contracts:

  * **Value contract** — every (sweep, block, causal, GQA group, ragged
    length, dtype) point matches the jnp oracle, and the two sweep orders
    agree *bitwise* at a fixed effective geometry: both kernels run the
    identical ``_online_update`` op sequence, so changing the sweep (like
    changing a GEMM dataflow) may change traffic but never bits.
  * **Residency contract** — a jaxpr regression pins that the kv-stationary
    path materializes no (rows, Skv) score tile in HBM; scores only ever
    exist as (bq, bk) VMEM blocks.
  * **Planning contract** — fake-timer CMU tests: the measured ranking (not
    the analytical model) picks the prefill schedule and the per-bucket
    decode kind, mirroring ``test_serving.test_bucket_tuning_is_
    measurement_driven``.
  * **Schema contract** — v6 plan caches load with ``attention=None`` and
    upgrade incrementally: every GEMM/decode/mesh decision survives
    verbatim, and the file re-persists as v7.
"""

import json
import os

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr
import numpy as np
import pytest
from _propcheck import given, settings, st

from repro.core import (
    attn_traffic_bytes,
    autotune_plan,
    hbm_traffic_bytes,
    load_or_autotune,
    load_plan,
    model_attn_shape,
    model_epilogues,
    model_gemms,
    plan_matches,
    save_plan,
)
from repro.core.plan_cache import PLAN_CACHE_VERSION
from repro.core import cmu as cmu_mod
from repro.kernels import (
    ATTN_SWEEPS,
    attention_ref,
    flex_attention,
    mha_flash,
    paged_attention,
    paged_attention_reference,
)
from repro.models import get_config

RNG = np.random.default_rng(7)


def _qkv(B, S, H, Hkv, hd, dtype=jnp.float32, skv=None):
    skv = skv or S
    q = jnp.asarray(RNG.normal(size=(B, S, H, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, skv, Hkv, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, skv, Hkv, hd)), dtype)
    return q, k, v


def _bits(x) -> bytes:
    return np.asarray(x).tobytes()


# ---------------------------------------------------------------------------
# property sweep: schedule variant x causal x GQA group x ragged length x dtype
# ---------------------------------------------------------------------------


@given(
    causal=st.booleans(),
    group=st.sampled_from([1, 2, 4]),
    seq=st.sampled_from([40, 56, 64, 120, 128]),
    dtype_name=st.sampled_from(["float32", "bfloat16"]),
)
@settings(max_examples=10, deadline=None)
def test_schedule_family_property_sweep(causal, group, seq, dtype_name):
    """Every schedule point matches the oracle; the two sweep orders agree
    bitwise (same effective blocks -> same op sequence -> same bits)."""
    dtype = jnp.dtype(dtype_name)
    Hkv = 2
    q, k, v = _qkv(1, seq, Hkv * group, Hkv, 32, dtype)
    outs = {
        sweep: mha_flash(q, k, v, causal=causal, interpret=True, sweep=sweep)
        for sweep in ATTN_SWEEPS
    }
    ref = attention_ref(q, k, v, causal=causal)
    atol = 2e-5 if dtype == jnp.float32 else 0.06
    for sweep, out in outs.items():
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=atol, rtol=atol, err_msg=f"sweep={sweep}")
    assert _bits(outs["q"]) == _bits(outs["kv"]), \
        "sweep order changed the bits: the variants diverged"


@given(bq=st.sampled_from([64, 128, 256]), bk=st.sampled_from([64, 128]))
@settings(max_examples=6, deadline=None)
def test_sweep_orders_agree_bitwise_per_block_shape(bq, bk):
    """At every (bq, bk) schedule knob setting the q- and kv-stationary
    kernels are bit-identical — the dataflow guarantee, attention edition."""
    q, k, v = _qkv(2, 256, 4, 2, 32)
    a = mha_flash(q, k, v, causal=True, interpret=True, block_q=bq,
                  block_k=bk, sweep="q")
    b = mha_flash(q, k, v, causal=True, interpret=True, block_q=bq,
                  block_k=bk, sweep="kv")
    assert _bits(a) == _bits(b)


def test_cross_attention_and_gqa_fold_shapes():
    """The GQA fold round-trips: output layout matches the oracle exactly
    for a non-causal cross-attention shape (longer KV, 4:1 group)."""
    q, k, v = _qkv(1, 96, 8, 2, 32, skv=160)
    out = mha_flash(q, k, v, causal=False, interpret=True)
    ref = attention_ref(q, k, v, causal=False)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# jaxpr regression: kv-stationary never materializes an HBM score tile
# ---------------------------------------------------------------------------


def _all_avals(jaxpr, acc):
    for eqn in jaxpr.eqns:
        acc.extend(v.aval for v in eqn.outvars)
        for val in eqn.params.values():
            for sub in _iter_jaxprs(val):
                _all_avals(sub, acc)
    return acc


def _iter_jaxprs(val):
    if isinstance(val, ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, Jaxpr):
        yield val
    elif isinstance(val, (list, tuple)):
        for item in val:
            yield from _iter_jaxprs(item)


def _has_score_matrix(fn, *args, S):
    avals = _all_avals(jax.make_jaxpr(fn)(*args).jaxpr, [])
    return any(
        getattr(a, "ndim", 0) >= 2 and a.shape[-1] == S and a.shape[-2] == S
        for a in avals)


def test_kv_stationary_materializes_no_score_tiles():
    """No intermediate anywhere in the jaxpr has a (rows, Skv) score shape:
    scores exist only as (bq, bk) VMEM tiles inside the kernel.  The jnp
    oracle (positive control) does materialize one."""
    S, hd = 256, 32
    q = jnp.zeros((4, S, hd), jnp.float32)
    kv = jnp.zeros((4, S, hd), jnp.float32)

    flex = lambda q, k, v: flex_attention(q, k, v, sweep="kv", causal=True,
                                          interpret=True)
    assert not _has_score_matrix(flex, q, kv, kv, S=S)

    q4 = jnp.zeros((1, S, 4, hd), jnp.float32)
    ref = lambda q, k, v: attention_ref(q, k, v, causal=True)
    assert _has_score_matrix(ref, q4, q4, q4, S=S), \
        "positive control failed: the detector no longer sees score tiles"


# ---------------------------------------------------------------------------
# paged decode kernel vs the gather oracle
# ---------------------------------------------------------------------------


def _paged_case(B=3, H=4, Hkv=2, hd=32, bs=16, nb=4, seed=0):
    rng = np.random.default_rng(seed)
    num_blocks = 1 + B * nb
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    pk = jnp.asarray(rng.normal(size=(num_blocks, bs, Hkv * hd)), jnp.float32)
    pv = jnp.asarray(rng.normal(size=(num_blocks, bs, Hkv * hd)), jnp.float32)
    table = 1 + jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    # ragged positions: each slot at a different depth, none block-aligned
    positions = jnp.asarray([bs * nb - 1, 5, 2 * bs + 3][:B], jnp.int32)
    return q, pk, pv, table, positions


def test_paged_decode_matches_reference():
    q, pk, pv, table, positions = _paged_case()
    out = paged_attention(q, pk, pv, table, positions, interpret=True)
    ref = paged_attention_reference(q, pk, pv, table, positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_paged_decode_sliding_window_fully_masked_blocks():
    """Masking-contract regression: with a sliding window deep into the
    cache, *whole leading K/V blocks* are masked.  The kernel must zero
    those probabilities multiplicatively — additive -1e30 bias alone leaves
    exp(s - m) == 1 per masked key when a block is fully dead, which
    silently averages garbage into the output."""
    q, pk, pv, table, positions = _paged_case()
    positions = jnp.full_like(positions, 16 * 4 - 1)  # deepest slot depth
    out = paged_attention(q, pk, pv, table, positions, window=8,
                          interpret=True)
    ref = paged_attention_reference(q, pk, pv, table, positions, window=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_paged_decode_live_slots_invariant_to_pad_rows():
    """The scheduler's bucket-padding guarantee, kernel edition: a pad row
    (table all-scratch, position 0) never perturbs live rows' outputs, no
    matter what garbage sits in the scratch block."""
    q, pk, pv, table, positions = _paged_case(B=3)
    # slot 2 becomes a pad row: scratch table, position 0
    table = table.at[2].set(0)
    positions = positions.at[2].set(0)
    out_a = paged_attention(q, pk, pv, table, positions, interpret=True)
    pk_b = pk.at[0].set(1e3)  # rewrite scratch with large garbage
    pv_b = pv.at[0].set(-1e3)
    out_b = paged_attention(q, pk_b, pv_b, table, positions, interpret=True)
    assert _bits(out_a[:2]) == _bits(out_b[:2]), \
        "scratch-block contents leaked into live slots"


# ---------------------------------------------------------------------------
# CMU planning: fake-timer tests + v6 -> v7 migration
# ---------------------------------------------------------------------------


CFG = lambda: get_config("qwen3_4b", smoke=True).replace(  # noqa: E731
    use_pallas=True, attn_pallas=True)
GEMMS = lambda cfg: model_gemms(cfg, tokens=64)  # noqa: E731


def _fast_gemm_timer(monkeypatch):
    """Route GEMM measurement through the analytical model so the attention
    planning tests don't spend their budget timing projection kernels."""
    monkeypatch.setattr(
        cmu_mod, "measure_kernel",
        lambda gemm, df, blk, **kw: hbm_traffic_bytes(gemm, df, *blk).time_s())


def test_attention_tuning_is_measurement_driven(monkeypatch):
    """Under a fake timer that penalizes whatever schedule the analytical
    model ranks first, the measured plan lands on a different (sweep,
    block) — the schedule comes from the timed execution, not the ranking."""
    cfg = CFG()
    attn = model_attn_shape(cfg, 64)
    analytic = autotune_plan(GEMMS(cfg), measure=False, attn=attn)
    ap0 = analytic.attention_plan()
    assert ap0 is not None and ap0.source == "analytical"
    pick = (ap0.sweep, ap0.block)

    def fake(shape, sweep, block, **kw):
        base = attn_traffic_bytes(shape, sweep, *block).time_s()
        return base * 100.0 if (sweep, tuple(block)) == pick else base

    _fast_gemm_timer(monkeypatch)
    monkeypatch.setattr(cmu_mod, "measure_attention", fake)
    plan = autotune_plan(GEMMS(cfg), measure=True, iters=1, attn=attn)
    ap = plan.attention_plan()
    assert ap is not None and ap.source == "measured"
    assert (ap.sweep, ap.block) != pick, \
        "measured tuning returned the penalized analytical pick"


@pytest.mark.parametrize("slow", ["paged", "gather"])
def test_attn_decode_kind_is_measurement_driven(monkeypatch, slow):
    """Per-bucket decode-kind choice follows the fake timer both ways:
    penalize 'paged' and the plan picks 'gather', and vice versa."""
    cfg = CFG()
    attn = model_attn_shape(cfg, 64)
    fast = {"paged": "gather", "gather": "paged"}[slow]

    def fake_decode(shape, bucket, kind, **kw):
        return 1.0 if kind == slow else 1e-6

    _fast_gemm_timer(monkeypatch)
    monkeypatch.setattr(
        cmu_mod, "measure_attention",
        lambda shape, sweep, block, **kw:
            attn_traffic_bytes(shape, sweep, *block).time_s())
    monkeypatch.setattr(cmu_mod, "measure_attention_decode", fake_decode)
    plan = autotune_plan(GEMMS(cfg), measure=True, iters=1, attn=attn,
                         decode_buckets=(8, 16))
    ap = plan.attention_plan()
    assert ap is not None and set(ap.decode) == {8, 16}
    for b, sub in ap.decode.items():
        assert sub.sweep == fast, (b, sub)
        assert sub.source == "measured"


def test_v6_cache_loads_with_attention_none_and_upgrades(tmp_path):
    """A v6 file (no attention rows) loads with attention=None; an
    attention-requesting load_or_autotune upgrades it incrementally — every
    GEMM, decode and mesh decision survives verbatim, only the attention
    schedule is tuned, and the file re-persists as v7."""
    cfg = CFG()
    attn = model_attn_shape(cfg, 64)
    plan = autotune_plan(GEMMS(cfg), measure=False, decode_buckets=(8,),
                         epilogue=model_epilogues(cfg))
    path = os.path.join(tmp_path, "plan.json")
    save_plan(path, plan)
    with open(path) as f:
        payload = json.load(f)
    payload["version"] = 6
    for row in payload["layers"]:
        row.pop("attention", None)
    with open(path, "w") as f:
        json.dump(payload, f)

    v6 = load_plan(path)
    assert all(lp.attention is None for lp in v6.layers)
    assert plan_matches(v6, GEMMS(cfg), buckets=(8,))  # attention-less: fine
    assert not plan_matches(v6, GEMMS(cfg), buckets=(8,), attn=attn)

    before = {
        lp.name: (lp.dataflow, lp.block, lp.strip, lp.bwd_dx, lp.bwd_dw,
                  lp.mesh, lp.decode)
        for lp in v6.layers
    }
    up, loaded = load_or_autotune(path, GEMMS(cfg), buckets=(8,), attn=attn,
                                  measure=False,
                                  epilogue=model_epilogues(cfg))
    assert not loaded  # it had to tune (the attention row)
    assert up.has_attention((8,))
    ap = up.attention_plan()
    assert ap is not None and ap.sweep in ATTN_SWEEPS and 8 in ap.decode
    for lp in up.layers:
        assert (lp.dataflow, lp.block, lp.strip, lp.bwd_dx, lp.bwd_dw,
                lp.mesh, lp.decode) == before[lp.name], \
            f"incremental attention upgrade retuned {lp.name}"
    with open(path) as f:
        assert json.load(f)["version"] == PLAN_CACHE_VERSION
    again, loaded = load_or_autotune(path, GEMMS(cfg), buckets=(8,),
                                     attn=attn, measure=False)
    assert loaded  # second launch reloads, no tuning
