"""Two-level stationarity: VMEM-resident accumulator strips.

Four acceptance bars:

* **Bit-identity property sweep** — for every dataflow x (trans_a, trans_b)
  x epilogue combination x ragged shape, the strip schedules must be
  bit-identical to ``strip=1`` streaming (same f32 MACs in the same k
  order; only residency differs).
* **Budget property** — every candidate ``_ranked_candidates`` emits fits
  ``VMEM_BUDGET_BYTES`` *including* the f32 accumulator-strip scratch, the
  strip tiles its axis exactly, and OS only ever carries strip=1.
* **Traffic model honesty** — ``hbm_traffic_bytes(strip=...)`` equals the
  byte count of a walk over the exact grid + index maps the kernel builders
  emit (``schedule_cost_bytes``), and strips eliminate the WS/IS
  partial-sum round-trips.
* **Schema v4** — v1/v2/v3 caches load-and-migrate with strip=1 (today's
  streamed behaviour, unchanged dispatch) and a migrated plan drives a
  correct end-to-end gradient.
"""

import json
import os
import sys
import tempfile

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr
import numpy as np
import pytest
from _propcheck import given, settings, st

import repro.kernels  # noqa: F401  — materialises the kernel submodules
from repro.core import (
    ALL_DATAFLOWS,
    TRANS_DX,
    TRANS_DW,
    VMEM_BUDGET_BYTES,
    Dataflow,
    GemmShape,
    autotune_plan,
    hbm_traffic_bytes,
    kernel_block_candidates,
    load_plan,
    strip_blocks,
    strip_candidates,
)
from repro.core.cmu import _ranked_candidates
from repro.kernels import flex_linear, flex_matmul, linear_ref

fk = sys.modules["repro.kernels.flex_matmul"]

RNG = np.random.default_rng(11)


def _rand(shape, dtype=jnp.float32, scale=0.2):
    return jnp.asarray(RNG.normal(size=shape) * scale, np.float32).astype(dtype)


def _physical(arr, trans: bool):
    return jnp.asarray(np.asarray(arr).T.copy()) if trans else arr


# ---------------------------------------------------------------------------
# bit-identity property sweep: strip vs streamed
# ---------------------------------------------------------------------------


@settings(max_examples=24, deadline=None)
@given(
    st.sampled_from([Dataflow.WS, Dataflow.IS]),
    st.booleans(),
    st.booleans(),
    st.integers(min_value=1, max_value=200),
    st.integers(min_value=1, max_value=200),
    st.integers(min_value=1, max_value=200),
    st.sampled_from([2, 3, 4, 8]),
)
def test_strip_matmul_bit_identical_to_streamed(df, ta, tb, M, K, N, strip):
    """Ragged shapes x trans layouts: ops pads and clamps the strip to the
    padded geometry; whatever depth actually runs must reproduce the
    streamed result bit-for-bit."""
    A, B = _rand((M, K)), _rand((K, N))
    a, b = _physical(A, ta), _physical(B, tb)
    kw = dict(dataflow=df, block=(64, 64, 64), interpret=True,
              trans_a=ta, trans_b=tb)
    streamed = flex_matmul(a, b, strip=1, **kw)
    stripped = flex_matmul(a, b, strip=strip, **kw)
    np.testing.assert_array_equal(np.asarray(streamed), np.asarray(stripped))


@settings(max_examples=12, deadline=None)
@given(
    st.sampled_from([Dataflow.WS, Dataflow.IS]),
    st.sampled_from([None, "relu", "gelu", "silu"]),
    st.booleans(),
    st.booleans(),
    st.integers(min_value=1, max_value=160),
    st.integers(min_value=1, max_value=160),
    st.sampled_from([2, 4]),
)
def test_strip_linear_bit_identical_to_streamed(df, act, bias, res, M, N, strip):
    """The fused epilogue off the strip flush (bias/activation/residual/cast)
    must match the streamed flush bit-for-bit — including the residual,
    which the strip kernel fuses in-kernel while the streamed path adds it
    outside in the same f32 op order."""
    K = 96
    x, w = _rand((M, K)), _rand((K, N))
    b = _rand((N,)) if bias else None
    r = _rand((M, N)) if res else None
    kw = dict(activation=act, residual=r, dataflow=df, block=(64, 64, 64),
              interpret=True, out_dtype=jnp.bfloat16)
    streamed = flex_linear(x, w, b, strip=1, **kw)
    stripped = flex_linear(x, w, b, strip=strip, **kw)
    np.testing.assert_array_equal(np.asarray(streamed), np.asarray(stripped))


@pytest.mark.parametrize("df", [Dataflow.WS, Dataflow.IS])
def test_strip_grad_bit_identical_to_streamed(df, strip=4):
    """save_preact + both backward GEMMs under strip schedules: gradients
    equal the streamed gradients bitwise and the XLA reference to tolerance."""
    x, w, b = _rand((128, 192)), _rand((192, 128)), _rand((128,))

    def loss(x, w, strip_fwd, st_dx, st_dw):
        # identical (dataflow, block, trans) for both runs — only the strip
        # depth differs, so any bit difference is the strip schedule's fault
        return flex_linear(x, w, b, activation="gelu", dataflow=df,
                           block=(64, 64, 64), interpret=True,
                           bwd_dx=(df, (64, 64, 64), TRANS_DX, st_dx),
                           bwd_dw=(df, (64, 64, 64), TRANS_DW, st_dw),
                           strip=strip_fwd).sum()

    g_stream = jax.grad(lambda x, w: loss(x, w, 1, 1, 1), (0, 1))(x, w)
    g_strip = jax.grad(
        lambda x, w: loss(x, w, strip, strip, strip), (0, 1)
    )(x, w)
    for gs, gt in zip(g_stream, g_strip):
        np.testing.assert_array_equal(np.asarray(gs), np.asarray(gt))
    g_ref = jax.grad(
        lambda x, w: linear_ref(x, w, b, activation="gelu").sum(), (0, 1)
    )(x, w)
    for gs, gr in zip(g_strip, g_ref):
        np.testing.assert_allclose(np.asarray(gs), np.asarray(gr),
                                   atol=2e-4, rtol=2e-4)


def test_os_rejects_strips_and_matmul_threads_them():
    a, b = _rand((128, 64)), _rand((64, 128))
    with pytest.raises(ValueError, match="OS runs strip=1"):
        fk.matmul(a, b, Dataflow.OS, block=(64, 64, 64), interpret=True,
                  strip=2)
    # the jitted wrapper normalises OS to strip=1 instead of erroring
    out = flex_matmul(a, b, Dataflow.OS, block=(64, 64, 64), interpret=True,
                      strip=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ b), atol=1e-5)


def test_strip_must_tile_axis_at_kernel_level():
    a, b = _rand((192, 64)), _rand((64, 64))  # 3 M-blocks of 64
    with pytest.raises(ValueError, match="must tile"):
        fk.matmul_ws(a, b, block=(64, 64, 64), interpret=True, strip=2)
    # the traffic walker rejects the same schedule instead of silently
    # walking a truncated grid
    with pytest.raises(ValueError, match="does not tile"):
        fk.schedule_cost_bytes(Dataflow.WS, 192, 64, 64, (64, 64, 64),
                               strip=2)
    # ops clamps 2 -> 1 for the same geometry (largest divisor of 3 <= 2)
    out = flex_matmul(a, b, Dataflow.WS, block=(64, 64, 64), interpret=True,
                      strip=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ b), atol=1e-5)


def test_strip_grid_axes_are_megacore_parallel():
    """The strip grids' (s, j/i) axes are single-writer, so the builders
    must declare them "parallel"; the streamed grids stay all-arbitrary
    (multi-writer output blocks across the k planes)."""

    def semantics(fn):
        jx = jax.make_jaxpr(fn)(jnp.ones((128, 64)), jnp.ones((64, 128)))

        def find(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "pallas_call":
                    return eqn
                for v in eqn.params.values():
                    for sub in (v if isinstance(v, (list, tuple)) else [v]):
                        if isinstance(sub, ClosedJaxpr):
                            got = find(sub.jaxpr)
                            if got is not None:
                                return got
            return None

        eqn = find(jx.jaxpr)
        assert eqn is not None
        return eqn.params["compiler_params"]["mosaic_tpu"].dimension_semantics

    blk = dict(block=(64, 64, 64), interpret=True)
    assert semantics(lambda a, b: fk.matmul_ws(a, b, strip=2, **blk)) == (
        "parallel", "parallel", "arbitrary", "arbitrary")
    assert semantics(lambda a, b: fk.matmul_is(a, b, strip=2, **blk)) == (
        "parallel", "parallel", "arbitrary", "arbitrary")
    assert semantics(lambda a, b: fk.matmul_ws(a, b, strip=1, **blk)) == (
        "arbitrary", "arbitrary", "arbitrary")


# ---------------------------------------------------------------------------
# traffic model: partial-sum elimination + schedule-walk agreement
# ---------------------------------------------------------------------------


def test_strip_eliminates_partial_sum_traffic():
    """For a strip-feasible shape the WS/IS strip traffic has no partial
    read-modify-write term: exactly one output write, with the stationary
    operand re-fetched once per strip."""
    g = GemmShape(1024, 1024, 1024)
    bm = bk = bn = 128
    kb = 8
    a, b, c = g.M * g.K * 2, g.K * g.N * 2, g.M * g.N * 4
    streamed = hbm_traffic_bytes(g, Dataflow.WS, bm, bk, bn).hbm_bytes
    assert streamed == b + (g.N // bn) * a + (2 * kb - 1) * c
    for strip in (2, 4, 8):
        got = hbm_traffic_bytes(g, Dataflow.WS, bm, bk, bn, strip=strip)
        sb = (g.M // bm) // strip
        assert got.hbm_bytes == sb * b + (g.N // bn) * a + c
        got_is = hbm_traffic_bytes(g, Dataflow.IS, bm, bk, bn, strip=strip)
        assert got_is.hbm_bytes == sb * a + (g.M // bm) * b + c
    # full-M residency: both the pinned operand and the outputs move once —
    # the WS floor, unreachable by any streamed schedule when Kb > 1
    full = hbm_traffic_bytes(g, Dataflow.WS, bm, bk, bn, strip=8).hbm_bytes
    assert full == b + (g.N // bn) * a + c < streamed


@pytest.mark.parametrize("df", ALL_DATAFLOWS)
def test_schedule_walk_matches_analytical_model(df):
    """The analytical model must agree with a walk over the exact grids and
    index maps the kernel builders emit (the CI perf smoke runs the same
    assertion on the benchmark shapes).

    The contract: byte-for-byte equality whenever every GEMM dimension
    spans >= 2 blocks (every shape the strip search targets), and a safe
    upper bound on degenerate single-block axes, where an idle grid axis
    leaves an index map constant and Pallas coalesces the refetch the
    closed form still charges."""
    for M, K, N, blk in [(256, 192, 256, (64, 64, 64)),
                         (512, 256, 256, (128, 128, 128)),
                         (128, 512, 512, (64, 128, 128))]:
        g = GemmShape(M, K, N)
        strips = [1] if df is Dataflow.OS else strip_candidates(
            strip_blocks(g, df, blk[0], blk[2]))
        for strip in strips:
            walk = fk.schedule_cost_bytes(df, M, K, N, blk, strip=strip,
                                          in_bytes=2, out_bytes=4)
            model = hbm_traffic_bytes(g, df, *blk, strip=strip).hbm_bytes
            assert walk == model, (df, strip, walk, model)
    # degenerate axes (single-block dims): the model upper-bounds the walk
    # (never undercounts, so VMEM/traffic pruning stays safe)
    for M, K, N, blk in [(512, 256, 128, (128, 128, 128)),
                         (64, 512, 64, (64, 64, 64)),
                         (64, 64, 640, (64, 64, 64))]:
        g = GemmShape(M, K, N)
        strips = [1] if df is Dataflow.OS else strip_candidates(
            strip_blocks(g, df, blk[0], blk[2]))
        for strip in strips:
            walk = fk.schedule_cost_bytes(df, M, K, N, blk, strip=strip,
                                          in_bytes=2, out_bytes=4)
            model = hbm_traffic_bytes(g, df, *blk, strip=strip).hbm_bytes
            assert walk <= model, (df, strip, walk, model)


def test_budget_property_every_candidate_fits_vmem():
    """Every (dataflow, block, strip) config the CMU ranks fits the unified
    VMEM budget including the strip's f32 scratch; strips tile their axis
    exactly; OS only ever emits strip=1."""
    for g in [GemmShape(4096, 1024, 4096), GemmShape(16, 896, 151_936),
              GemmShape(65_536, 2560, 9728)]:
        ranked = _ranked_candidates(g, VMEM_BUDGET_BYTES)
        assert ranked
        saw_strip = False
        for t, df, (bm, bk, bn), strip, _qd in ranked:
            cost = hbm_traffic_bytes(g, df, bm, bk, bn, strip=strip)
            assert cost.vmem_bytes <= VMEM_BUDGET_BYTES
            # what Mosaic allocates: double-buffered operand, bias-row and
            # residual blocks, double-buffered output + f32 staging blocks
            # over the strip, OS's f32 scratch, three f32 temporaries
            rows = strip * bm if df is Dataflow.WS else bm
            cols = strip * bn if df is Dataflow.IS else bn
            tile = lambda r, c, b: (-(-r // (32 // b)) * (32 // b)
                                    * -(-c // 128) * 128 * b)
            ins = (max(tile(bm, bk, 2), tile(bk, bm, 2))
                   + max(tile(bk, bn, 2), tile(bn, bk, 2))
                   + tile(1, cols, 4) + tile(rows, cols, 4))
            outs = 2 * tile(rows, cols, 4)
            scratch = tile(bm, bn, 4) if df is Dataflow.OS else 0
            recomputed = (2 * ins + 2 * outs + scratch
                          + 3 * tile(bm, bn, 4))
            assert cost.vmem_bytes == recomputed
            if df is Dataflow.OS:
                assert strip == 1
            else:
                assert strip_blocks(g, df, bm, bn) % strip == 0
                saw_strip = saw_strip or strip > 1
            assert t > 0
        assert saw_strip  # the 3-D schedule space is actually searched


def test_strip_beats_streamed_for_deep_k_ws():
    """The motivating shape: K spans many blocks, so streamed WS pays
    (2Kb-1) output round-trips and loses to OS for an artifact reason;
    the strip schedule removes them and the analytical argmin for a tall
    deep-K GEMM becomes a WS/IS strip schedule, not OS."""
    g = GemmShape(8192, 8192, 256)  # tall, deep K, narrow N
    ranked = _ranked_candidates(g, VMEM_BUDGET_BYTES)
    best_t, best_df, best_blk, best_strip, _qd = ranked[0]
    best = hbm_traffic_bytes(g, best_df, *best_blk, strip=best_strip)
    streamed_best = min(
        hbm_traffic_bytes(g, df, bm, bk, bn).hbm_bytes
        for _, df, (bm, bk, bn), s, _q in ranked if s == 1
    )
    assert best.hbm_bytes <= streamed_best
    stripped = [r for r in ranked if r[3] > 1]
    assert stripped and min(s[0] for s in stripped) <= ranked[0][0] + 1e-18


# ---------------------------------------------------------------------------
# skinny decode blocks
# ---------------------------------------------------------------------------


def test_skinny_block_candidates_for_small_m():
    assert kernel_block_candidates(8, sublane=True)[0] == 8
    assert kernel_block_candidates(32, sublane=True)[:3] == [8, 16, 32]
    # K/N dimensions keep the MXU-aligned floor of 128
    assert min(kernel_block_candidates(32)) == 128
    # large dims are unchanged by the sublane flag
    assert kernel_block_candidates(4096, sublane=True) == \
        kernel_block_candidates(4096)


def test_decode_geometry_plans_skinny_blocks():
    """A decode-step projection (M = batch = 16) must tune to a sublane
    block, not pad to 128+ rows, and the plan must survive the cache."""
    from repro.core import plan_matches, save_plan

    g = GemmShape(16, 896, 1024, name="attn.wq")
    plan = autotune_plan([g], top_k=2, iters=1)
    lp = plan.layers[0]
    assert lp.block is not None and lp.block[0] <= 64
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "plan.json")
        save_plan(p, plan)
        reloaded = load_plan(p)
        assert plan_matches(reloaded, [g])
        assert reloaded.layers[0].block == lp.block
        assert reloaded.layers[0].strip == lp.strip


# ---------------------------------------------------------------------------
# plan-cache schema v4: v1/v2/v3 load-and-migrate with strip=1 semantics
# ---------------------------------------------------------------------------


def _v3_payload():
    return {
        "version": 3,
        "layers": [{
            "name": "mlp.w1", "M": 128, "K": 96, "N": 128,
            "dataflow": "WS", "est_cost": 1.0,
            "block": [64, 96, 64], "source": "measured",
            "bwd_dx": {"dataflow": "IS", "block": [64, 64, 96],
                       "est_cost": 0.9, "source": "measured",
                       "trans": [False, True]},
            "bwd_dw": {"dataflow": "OS", "block": [96, 64, 64],
                       "est_cost": 0.8, "source": "measured",
                       "trans": [True, False]},
        }],
    }


@pytest.mark.parametrize("version", [1, 2, 3])
def test_old_caches_migrate_to_strip1_with_unchanged_dispatch(version):
    payload = _v3_payload()
    payload["version"] = version
    if version < 3:
        for sub in ("bwd_dx", "bwd_dw"):
            payload["layers"][0][sub].pop("trans")
    if version < 2:
        payload["layers"][0]["bwd_dx"] = None
        payload["layers"][0]["bwd_dw"] = None
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "plan.json")
        with open(p, "w") as f:
            json.dump(payload, f)
        plan = load_plan(p)
    lp = plan.layers[0]
    # dispatch unchanged: same dataflow/block as the old plan, strip=1
    # (exactly the streamed schedule every pre-v4 plan was tuned on)
    assert lp.dataflow is Dataflow.WS and lp.block == (64, 96, 64)
    assert lp.strip == 1
    if version >= 2:
        assert lp.bwd_dx.strip == 1 and lp.bwd_dw.strip == 1
        assert lp.bwd_dx.trans == TRANS_DX and lp.bwd_dw.trans == TRANS_DW


def test_migrated_v3_plan_drives_correct_end_to_end_grad():
    """End-to-end: a migrated v3 cache's specs (now carrying strip=1) reach
    the VJP, produce reference gradients, and match the streamed dispatch
    bit-for-bit — today's behaviour, reproduced."""
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "plan.json")
        with open(p, "w") as f:
            json.dump(_v3_payload(), f)
        lp = load_plan(p).layers[0]
    x, w = _rand((128, 96)), _rand((96, 128))
    dx_spec = (lp.bwd_dx.dataflow, lp.bwd_dx.block, lp.bwd_dx.trans,
               lp.bwd_dx.strip)
    dw_spec = (lp.bwd_dw.dataflow, lp.bwd_dw.block, lp.bwd_dw.trans,
               lp.bwd_dw.strip)

    def loss(x, w):
        return flex_linear(x, w, activation="gelu", dataflow=lp.dataflow,
                           block=lp.block, interpret=True, strip=lp.strip,
                           bwd_dx=dx_spec, bwd_dw=dw_spec).sum()

    def legacy(x, w):  # the pre-v4 dispatch: identical but with 3-tuple specs
        return flex_linear(x, w, activation="gelu", dataflow=lp.dataflow,
                           block=lp.block, interpret=True,
                           bwd_dx=dx_spec[:3], bwd_dw=dw_spec[:3]).sum()

    got = jax.grad(loss, (0, 1))(x, w)
    old = jax.grad(legacy, (0, 1))(x, w)
    want = jax.grad(
        lambda x, w: linear_ref(x, w, activation="gelu").sum(), (0, 1)
    )(x, w)
    for g, o, r in zip(got, old, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(o))
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=2e-4, rtol=2e-4)


def test_train_plan_records_strips_and_runs():
    """A fresh measured train plan over a strip-favourable geometry records
    its (dataflow, block, strip) decisions and drives a correct grad."""
    plan = autotune_plan([GemmShape(64, 128, 64, name="l0")], top_k=2,
                         iters=1, train=True)
    lp = plan.layers[0]
    assert lp.strip >= 1 and lp.bwd_dx.strip >= 1 and lp.bwd_dw.strip >= 1
    x, w = _rand((64, 128)), _rand((128, 64))
    dx = (lp.bwd_dx.dataflow, lp.bwd_dx.block, lp.bwd_dx.trans, lp.bwd_dx.strip)
    dw = (lp.bwd_dw.dataflow, lp.bwd_dw.block, lp.bwd_dw.trans, lp.bwd_dw.strip)
    got = jax.grad(
        lambda x, w: flex_linear(x, w, activation="silu", dataflow=lp.dataflow,
                                 block=lp.block, strip=lp.strip, interpret=True,
                                 bwd_dx=dx, bwd_dw=dw).sum(), (0, 1)
    )(x, w)
    want = jax.grad(
        lambda x, w: linear_ref(x, w, activation="silu").sum(), (0, 1)
    )(x, w)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=2e-4, rtol=2e-4)
