"""Transpose-free backward GEMMs: transposed-operand kernels, CMU
re-ranking, plan-cache schema v3.

Three acceptance bars:

* **Property sweep** — for every dataflow x (trans_a, trans_b) x ragged
  (non-block-multiple) shape x dtype, ``ops.flex_matmul`` (interpret mode)
  must match ``jnp.matmul`` on the logical operands to tolerance.
* **Jaxpr regression** — the backward of ``flex_linear``/``flex_matmul``
  under the (default) transposed-operand specs must contain **no**
  ``transpose`` equations anywhere (the HBM copy must not sneak back); the
  explicit copy-based spec must still produce one (proving the probe sees
  transposes at all).
* **Honest CMU** — backward sub-GEMMs are timed as the transposed-variant
  kernels plus the copy-based fallback *with its transpose cost included*;
  the winning operand layout lands in ``GemmPlan.trans``, survives the v3
  cache roundtrip, and v1/v2 files load-and-migrate.
"""

import json
import os
import tempfile

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr
import numpy as np
import pytest
from _propcheck import given, settings, st

from repro.core import (
    ALL_DATAFLOWS,
    NO_TRANS,
    TRANS_DX,
    TRANS_DW,
    Dataflow,
    EpilogueSig,
    GemmShape,
    autotune_plan,
    hbm_traffic_bytes,
    load_plan,
    measure_kernel,
    save_plan,
)
from repro.core import cmu as cmu_mod
from repro.core import plan_cache as plan_cache_mod
from repro.kernels import flex_linear, flex_matmul, linear_ref

RNG = np.random.default_rng(7)


def _rand(shape, dtype=jnp.float32, scale=0.2):
    return jnp.asarray(RNG.normal(size=shape) * scale, np.float32).astype(dtype)


def _physical(arr, trans: bool):
    """Store ``arr`` in transposed physical layout when ``trans``."""
    return jnp.asarray(np.asarray(arr).T.copy()) if trans else arr


# ---------------------------------------------------------------------------
# property-based kernel sweep: dataflow x trans x ragged shapes x dtypes
# ---------------------------------------------------------------------------


@settings(max_examples=24, deadline=None)
@given(
    st.sampled_from(ALL_DATAFLOWS),
    st.booleans(),
    st.booleans(),
    st.integers(min_value=1, max_value=200),
    st.integers(min_value=1, max_value=200),
    st.integers(min_value=1, max_value=200),
    st.sampled_from(["float32", "bfloat16"]),
)
def test_flex_matmul_matches_jnp_under_transposition(df, ta, tb, M, K, N, dt):
    dtype = jnp.dtype(dt)
    A = _rand((M, K), dtype)
    B = _rand((K, N), dtype)
    out = flex_matmul(
        _physical(A, ta), _physical(B, tb), dataflow=df, interpret=True,
        trans_a=ta, trans_b=tb,
    )
    ref = jnp.matmul(A, B, preferred_element_type=jnp.float32).astype(out.dtype)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol, rtol=tol,
    )


@settings(max_examples=12, deadline=None)
@given(
    st.sampled_from(ALL_DATAFLOWS),
    st.booleans(),
    st.booleans(),
    st.integers(min_value=1, max_value=160),
    st.integers(min_value=1, max_value=160),
    st.integers(min_value=1, max_value=160),
)
def test_flex_matmul_grads_match_under_transposition(df, ta, tb, M, K, N):
    """The VJP is itself transpose-free for every flag combination and must
    produce the reference cotangents in the *stored* layouts."""
    A, B = _rand((M, K)), _rand((K, N))
    a, b = _physical(A, ta), _physical(B, tb)

    def loss(a, b):
        return (flex_matmul(a, b, dataflow=df, interpret=True,
                            trans_a=ta, trans_b=tb) ** 2).sum()

    def ref(a, b):
        aa = a.T if ta else a
        bb = b.T if tb else b
        return (jnp.matmul(aa, bb, preferred_element_type=jnp.float32) ** 2).sum()

    got = jax.grad(loss, (0, 1))(a, b)
    want = jax.grad(ref, (0, 1))(a, b)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=5e-3, rtol=5e-3)


# ---------------------------------------------------------------------------
# jaxpr regression: the HBM transpose copy must not sneak back
# ---------------------------------------------------------------------------


def _all_primitives(jaxpr, out=None):
    """Every primitive name in ``jaxpr``, recursing into sub-jaxprs (pjit
    bodies, custom-vjp closures, pallas kernels)."""
    out = set() if out is None else out
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if isinstance(sub, ClosedJaxpr):
                    _all_primitives(sub.jaxpr, out)
                elif isinstance(sub, Jaxpr):
                    _all_primitives(sub, out)
    return out


def _grad_prims(fn, *args):
    argnums = tuple(range(len(args)))
    return _all_primitives(jax.make_jaxpr(jax.grad(fn, argnums))(*args).jaxpr)


@pytest.mark.parametrize("df", ALL_DATAFLOWS)
def test_linear_backward_issues_no_transpose(df):
    """dX/dW under the default (transposed-operand) specs: zero transpose
    equations anywhere in the grad jaxpr, for all three dataflows."""
    x, w, b = _rand((96, 200)), _rand((200, 130)), _rand((130,))

    def loss(x, w, b):
        return flex_linear(x, w, b, activation="gelu", dataflow=df,
                           interpret=True).sum()

    assert "transpose" not in _grad_prims(loss, x, w, b)


def test_linear_backward_planned_trans_specs_issue_no_transpose():
    """Plan-supplied 3-tuple specs with the zero-copy layouts stay clean."""
    x, w = _rand((64, 96)), _rand((96, 72))

    def loss(x, w):
        return flex_linear(
            x, w, activation="silu", interpret=True,
            bwd_dx=(Dataflow.WS, (64, 72, 96), TRANS_DX),
            bwd_dw=(Dataflow.IS, (96, 64, 72), TRANS_DW),
        ).sum()

    assert "transpose" not in _grad_prims(loss, x, w)


def test_matmul_backward_issues_no_transpose():
    a, b = _rand((64, 96)), _rand((96, 72))

    def loss(a, b):
        return (flex_matmul(a, b, interpret=True) ** 2).sum()

    assert "transpose" not in _grad_prims(loss, a, b)


def test_copy_based_spec_still_issues_transpose():
    """Sanity check of the probe itself: an explicit (False, False) spec —
    the copy-based fallback a measured plan may legitimately program — does
    materialise the HBM transpose, so the assertions above are meaningful."""
    x, w = _rand((64, 96)), _rand((96, 72))

    def loss(x, w):
        return flex_linear(
            x, w, interpret=True,
            bwd_dx=(Dataflow.OS, None, NO_TRANS),
            bwd_dw=(Dataflow.OS, None, NO_TRANS),
        ).sum()

    assert "transpose" in _grad_prims(loss, x, w)


def test_legacy_2tuple_bwd_specs_default_to_zero_copy():
    """Pre-v3 (dataflow, block) specs inherit the transposed-operand default
    — and still produce reference gradients."""
    x, w, b = _rand((64, 96)), _rand((96, 72)), _rand((72,))

    def loss(x, w):
        return flex_linear(x, w, b, activation="gelu", interpret=True,
                           bwd_dx=(Dataflow.WS, (64, 72, 96)),
                           bwd_dw=(Dataflow.IS, (96, 64, 72))).sum()

    assert "transpose" not in _grad_prims(loss, x, w)
    got = jax.grad(loss, (0, 1))(x, w)
    want = jax.grad(
        lambda x, w: linear_ref(x, w, b, activation="gelu").sum(), (0, 1)
    )(x, w)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# honest CMU: transposed-variant vs copy-based candidates
# ---------------------------------------------------------------------------


def test_measure_kernel_times_transposed_and_copy_variants():
    g = GemmShape(64, 96, 64, name="probe.dx")
    t_zero = measure_kernel(g, Dataflow.OS, (64, 96, 64), iters=1,
                            trans=TRANS_DX, interpret=True)
    t_copy = measure_kernel(g, Dataflow.OS, (64, 96, 64), iters=1,
                            trans=TRANS_DX, via_copy=True, interpret=True)
    assert t_zero > 0 and t_copy > 0


def test_train_plan_bwd_subplans_carry_trans(monkeypatch):
    """Under a deterministic fake timer that charges the copy variant a
    penalty, both sub-plans pick the zero-copy layout; when the fake makes
    the copy free, the plan records the copy-based fallback instead — the
    re-ranking is driven by the measurement, not hardwired."""
    def fake_cheap_zero_copy(gemm, df, blk, **kw):
        base = hbm_traffic_bytes(gemm, df, *blk).time_s()
        return base * 10.0 if kw.get("via_copy") else base

    monkeypatch.setattr(cmu_mod, "measure_kernel", fake_cheap_zero_copy)
    plan = autotune_plan([GemmShape(64, 96, 64, name="l0")], top_k=2,
                         iters=1, train=True)
    lp = plan.layers[0]
    assert lp.bwd_dx.trans == TRANS_DX and lp.bwd_dw.trans == TRANS_DW
    assert lp.bwd_dx.source == "measured"

    def fake_cheap_copy(gemm, df, blk, **kw):
        base = hbm_traffic_bytes(gemm, df, *blk).time_s()
        return base * 0.1 if kw.get("via_copy") else base

    monkeypatch.setattr(cmu_mod, "measure_kernel", fake_cheap_copy)
    plan2 = autotune_plan([GemmShape(64, 96, 64, name="l0")], top_k=2,
                          iters=1, train=True)
    lp2 = plan2.layers[0]
    assert lp2.bwd_dx.trans == NO_TRANS and lp2.bwd_dw.trans == NO_TRANS


def test_unmeasured_bwd_subplans_default_to_zero_copy():
    """Analytically the zero-copy variant strictly dominates (same kernel
    traffic minus the copy), so measurement-off plans program it."""
    plan = autotune_plan([GemmShape(64, 96, 64, name="l0")], measure=False,
                         train=True)
    lp = plan.layers[0]
    assert lp.bwd_dx.trans == TRANS_DX and lp.bwd_dw.trans == TRANS_DW
    assert lp.bwd_dx.source == "analytical"


def test_real_measured_train_plan_runs_end_to_end():
    """No fakes: a real measured train plan tunes both layouts and its specs
    drive a correct grad through flex_linear."""
    plan = autotune_plan([GemmShape(32, 64, 32, name="l0")], top_k=1,
                         iters=1, train=True)
    lp = plan.layers[0]
    assert lp.bwd_dx.source == "measured"
    x, w = _rand((32, 64)), _rand((64, 32))
    dx_spec = (lp.bwd_dx.dataflow, lp.bwd_dx.block, lp.bwd_dx.trans)
    dw_spec = (lp.bwd_dw.dataflow, lp.bwd_dw.block, lp.bwd_dw.trans)
    got = jax.grad(
        lambda x, w: flex_linear(x, w, activation="gelu", interpret=True,
                                 bwd_dx=dx_spec, bwd_dw=dw_spec).sum(), (0, 1)
    )(x, w)
    want = jax.grad(
        lambda x, w: linear_ref(x, w, activation="gelu").sum(), (0, 1)
    )(x, w)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# epilogue-aware autotune under a deterministic fake timer
# ---------------------------------------------------------------------------


def _rank_reversing_timer(seen):
    """Fake timer keyed on measurement order: bare candidates cost their
    call index (the first-measured, i.e. analytically-best, survivor wins);
    epilogue-sig candidates cost the *negated* index (the last-measured
    survivor wins).  Deterministic, and guarantees the two plans pick
    distinct (dataflow, block) configs whenever ``top_k > 1``."""

    def fake(gemm, df, blk, **kw):
        seen.append(kw.get("epilogue"))
        idx = float(len(seen))
        sig = kw.get("epilogue")
        if isinstance(sig, EpilogueSig) and sig.activation:
            return -idx
        return idx

    return fake


def test_epilogue_sig_reaches_the_timer_and_reranks(monkeypatch):
    seen = []
    monkeypatch.setattr(cmu_mod, "measure_kernel", _rank_reversing_timer(seen))
    gemms = [GemmShape(256, 512, 128, name="mlp.w1")]
    sig = {"mlp.w1": EpilogueSig(activation="gelu")}
    bare = autotune_plan(gemms, top_k=3, iters=1)
    fused = autotune_plan(gemms, top_k=3, iters=1, epilogue=sig)
    assert any(isinstance(s, EpilogueSig) for s in seen)
    b, f = bare.layers[0], fused.layers[0]
    assert (b.dataflow, b.block) != (f.dataflow, f.block)
    # determinism: identical inputs -> identical plans, both runs
    bare2 = autotune_plan(gemms, top_k=3, iters=1)
    fused2 = autotune_plan(gemms, top_k=3, iters=1, epilogue=sig)
    assert (bare2.layers[0].dataflow, bare2.layers[0].block) == (b.dataflow, b.block)
    assert (fused2.layers[0].dataflow, fused2.layers[0].block) == (f.dataflow, f.block)


def test_epilogue_dict_miss_means_bare_probe(monkeypatch):
    """A layer absent from the epilogue dict is timed as the bare matmul —
    its plan equals the bool-False plan under the same fake timer."""
    seen = []
    monkeypatch.setattr(cmu_mod, "measure_kernel", _rank_reversing_timer(seen))
    gemms = [GemmShape(256, 512, 128, name="attn.wq")]
    miss = autotune_plan(gemms, top_k=3, iters=1,
                         epilogue={"other": EpilogueSig(activation="gelu")})
    bare = autotune_plan(gemms, top_k=3, iters=1)
    assert (miss.layers[0].dataflow, miss.layers[0].block) == (
        bare.layers[0].dataflow, bare.layers[0].block)


def test_measure_kernel_accepts_full_epilogue_signature():
    g = GemmShape(32, 64, 32, name="mlp.w2")
    t = measure_kernel(g, Dataflow.OS, (32, 64, 32), iters=1, interpret=True,
                       epilogue=EpilogueSig(activation="silu", bias=True,
                                            residual=True))
    assert t > 0


def test_model_epilogues_match_layer_call_sites():
    from repro.core import model_epilogues
    from repro.models import get_config

    cfg = get_config("qwen3_4b", smoke=True)
    sigs = model_epilogues(cfg)
    assert sigs["mlp.w1"].activation in ("silu", "gelu")
    assert sigs["mlp.w2"].residual and sigs["attn.wo"].residual
    assert sigs["lm_head"] == EpilogueSig()
    assert sigs["attn.wq"].bias == cfg.qkv_bias


# ---------------------------------------------------------------------------
# plan-cache schema v3 + v1/v2 load-and-migrate
# ---------------------------------------------------------------------------


def _v2_payload():
    return {
        "version": 2,
        "layers": [{
            "name": "attn.wq", "M": 64, "K": 96, "N": 64,
            "dataflow": "OS", "est_cost": 1.0,
            "block": [64, 128, 64], "source": "measured",
            "bwd_dx": {"dataflow": "IS", "block": [64, 64, 128],
                       "est_cost": 0.9, "source": "measured"},
            "bwd_dw": {"dataflow": "WS", "block": [128, 64, 64],
                       "est_cost": 0.8, "source": "measured"},
        }],
    }


def test_v2_cache_migrates_bwd_subplans_to_zero_copy():
    """v2 sub-plans (tuned on pre-transposed operands) keep their
    (dataflow, block) — valid for the same logical GEMM — and are assigned
    their role's zero-copy layout."""
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "plan.json")
        with open(p, "w") as f:
            json.dump(_v2_payload(), f)
        plan = load_plan(p)
        lp = plan.layers[0]
        assert plan.has_bwd()
        assert lp.bwd_dx.trans == TRANS_DX and lp.bwd_dw.trans == TRANS_DW
        assert lp.bwd_dx.dataflow is Dataflow.IS
        assert lp.bwd_dx.block == (64, 64, 128)


def test_v1_cache_still_loads_fwd_only():
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "plan.json")
        with open(p, "w") as f:
            json.dump({"version": 1, "layers": [{
                "name": "attn.wq", "M": 64, "K": 96, "N": 64,
                "dataflow": "OS", "est_cost": 1.0,
                "block": [64, 128, 64], "source": "measured"}]}, f)
        plan = load_plan(p)
        assert plan.layers[0].bwd_dx is None and not plan.has_bwd()


def test_roundtrip_preserves_trans_and_writes_current_schema():
    plan = autotune_plan([GemmShape(64, 96, 64, name="l0")], measure=False,
                         train=True)
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "plan.json")
        save_plan(p, plan)
        with open(p) as f:
            payload = json.load(f)
        assert payload["version"] == plan_cache_mod.PLAN_CACHE_VERSION
        assert payload["layers"][0]["bwd_dx"]["trans"] == [False, True]
        assert "strip" in payload["layers"][0]
        assert "strip" in payload["layers"][0]["bwd_dx"]
        plan2 = load_plan(p)
        assert plan2.layers == plan.layers


def test_migrated_v2_plan_drives_transpose_free_backward():
    """End-to-end: a migrated v2 cache's specs reach the VJP and the grad
    jaxpr stays free of transpose equations."""
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "plan.json")
        with open(p, "w") as f:
            json.dump(_v2_payload(), f)
        lp = load_plan(p).layers[0]
    x, w = _rand((64, 96)), _rand((96, 64))
    dx_spec = (lp.bwd_dx.dataflow, lp.bwd_dx.block, lp.bwd_dx.trans)
    dw_spec = (lp.bwd_dw.dataflow, lp.bwd_dw.block, lp.bwd_dw.trans)

    def loss(x, w):
        return flex_linear(x, w, activation="gelu", interpret=True,
                           bwd_dx=dx_spec, bwd_dw=dw_spec).sum()

    assert "transpose" not in _grad_prims(loss, x, w)
    got = jax.grad(loss, (0, 1))(x, w)
    want = jax.grad(
        lambda x, w: linear_ref(x, w, activation="gelu").sum(), (0, 1)
    )(x, w)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=2e-4, rtol=2e-4)


def test_migration_is_idempotent_and_counts():
    # a v2 row migrating to v4 gains: 2 sub-plan trans layouts + 3 strip=1
    # defaults (fwd row + both sub-plans) = 5 migrated fields
    rows = _v2_payload()["layers"]
    assert plan_cache_mod._migrate_rows(rows, 2) == 5
    assert plan_cache_mod._migrate_rows(rows, 2) == 0  # already migrated
    # a v3 row only gains the strip=1 fields
    v3_rows = _v2_payload()["layers"]
    for row in v3_rows:
        row["bwd_dx"]["trans"] = [False, True]
        row["bwd_dw"]["trans"] = [True, False]
    assert plan_cache_mod._migrate_rows(v3_rows, 3) == 3
    assert plan_cache_mod._migrate_rows(v3_rows, 4) == 0  # v4 untouched
