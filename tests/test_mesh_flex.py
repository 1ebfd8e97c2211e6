"""Mesh-native flex kernel tests.

Two tiers:

* single-device tests (always run): the mesh planning level of the CMU —
  local-shape math, ``MeshPlan`` serialization, plan-cache schema v5 with
  the mesh fingerprint, v4 migration + incremental upgrade, and the
  ``dp_size`` single-definition pin.
* multi-device tests (skipped unless jax has >= 8 devices — the CI
  ``multi-device`` lane runs this file under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``): the
  shard_map-composed kernels themselves — each mesh dataflow against the
  XLA reference for forward and ``jax.grad``, the ``models.layers.linear``
  routing + fallback contract, and the involuntary-replication warning.
"""

import dataclasses
import json
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Dataflow,
    GemmShape,
    MeshSpec,
    autotune_plan,
    mesh_local_gemm,
    mesh_shardable,
)
from repro.core.plan_cache import (
    PLAN_CACHE_VERSION,
    activate_plan,
    load_or_autotune,
    load_plan,
    plan_matches,
    save_plan,
)

multi_device = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)

MESH_SPEC = MeshSpec(axes=(("data", 2), ("model", 4)), dp_axes=("data",))
TUNE_KW = dict(measure=False)  # analytical-only: no kernel timing in tests


# ---------------------------------------------------------------------------
# single-device: the mesh planning level
# ---------------------------------------------------------------------------


def test_mesh_local_gemm_shapes():
    g = GemmShape(256, 64, 128, name="p")
    assert mesh_local_gemm(g, Dataflow.WS, tp=4, dp=2) == GemmShape(128, 16, 128, name="p.shard")
    assert mesh_local_gemm(g, Dataflow.IS, tp=4, dp=2) == GemmShape(32, 64, 128, name="p.shard")
    assert mesh_local_gemm(g, Dataflow.OS, tp=4, dp=2) == GemmShape(32, 16, 128, name="p.shard")


def test_mesh_shardable_gate():
    assert mesh_shardable(GemmShape(256, 64, 128), tp=4, dp=2)
    assert not mesh_shardable(GemmShape(250, 64, 128), tp=4, dp=2)  # M ragged
    assert not mesh_shardable(GemmShape(256, 62, 128), tp=4, dp=2)  # K ragged
    assert not mesh_shardable(GemmShape(256, 64, 128), tp=1)        # no TP


def _tuned_plan(train=True):
    gemms = [GemmShape(256, 64, 128, name="mlp.w1"),
             GemmShape(256, 128, 64, name="mlp.w2")]
    return gemms, autotune_plan(gemms, train=train, mesh=MESH_SPEC, **TUNE_KW)


def test_mesh_subplans_tuned_for_post_collective_shapes():
    _, plan = _tuned_plan()
    assert plan.mesh == MESH_SPEC
    for lp in plan.layers:
        mp = lp.mesh
        assert mp is not None and mp.tp == 4 and mp.dp == 2
        assert mp.axis == "model"
        assert mp.local is not None and mp.local_dx is not None
        lshape = mesh_local_gemm(lp.gemm, mp.dataflow, mp.tp, mp.dp)
        # the local block never exceeds the (rounded) local shard dims —
        # evidence the chip-level tuner saw the post-collective shape
        bm, bk, bn = mp.local.block
        assert bm <= max(lshape.M, 128) and bk <= max(lshape.K, 128)
        assert mp.comm_bytes > 0


def test_non_dividing_layer_gets_no_mesh_subplan():
    gemms = [GemmShape(250, 64, 128, name="ragged")]
    plan = autotune_plan(gemms, mesh=MESH_SPEC, **TUNE_KW)
    assert plan.layers[0].mesh is None  # falls back at dispatch


def test_plan_json_roundtrip_with_mesh(tmp_path):
    from repro.core import DataflowPlan

    _, plan = _tuned_plan()
    assert DataflowPlan.from_json(plan.to_json()).layers == plan.layers
    p = tmp_path / "plan.json"
    save_plan(str(p), plan)
    loaded = load_plan(str(p))
    assert loaded.mesh == MESH_SPEC
    assert loaded.layers == plan.layers
    assert json.load(open(p))["version"] == PLAN_CACHE_VERSION


def _as_v4_file(v5_path, v4_path):
    """Strip the v5-only fields, producing the file a v4 build would write."""
    payload = json.load(open(v5_path))
    payload["version"] = 4
    payload.pop("mesh")
    for row in payload["layers"]:
        row.pop("mesh")
    json.dump(payload, open(v4_path, "w"))


def test_v4_cache_loads_as_single_device_bit_for_bit(tmp_path):
    gemms, plan = _tuned_plan()
    v5, v4 = tmp_path / "v5.json", tmp_path / "v4.json"
    save_plan(str(v5), plan)
    _as_v4_file(v5, v4)
    loaded = load_plan(str(v4))
    assert loaded.mesh is None
    # every single-device decision identical — dispatch is bit-for-bit
    assert [dataclasses.replace(l, mesh=None) for l in plan.layers] \
        == list(loaded.layers)
    # and it still matches a single-device request (loads without re-tune)
    assert plan_matches(loaded, gemms, require_bwd=True)
    got, was_loaded = load_or_autotune(str(v4), gemms, require_bwd=True,
                                       **TUNE_KW)
    assert was_loaded and got.layers == loaded.layers


def test_v4_cache_migrates_to_v5_mesh_incrementally(tmp_path):
    gemms, plan = _tuned_plan()
    v5, v4 = tmp_path / "v5.json", tmp_path / "v4.json"
    save_plan(str(v5), plan)
    _as_v4_file(v5, v4)
    # a mesh request on the v4 file must not match as-is...
    assert not plan_matches(load_plan(str(v4)), gemms, mesh=MESH_SPEC)
    # ...and upgrades incrementally: single-device rows kept verbatim,
    # mesh sub-plans added, file rewritten at v5
    got, was_loaded = load_or_autotune(str(v4), gemms, require_bwd=True,
                                       mesh=MESH_SPEC, **TUNE_KW)
    assert not was_loaded
    assert [dataclasses.replace(l, mesh=None) for l in got.layers] \
        == [dataclasses.replace(l, mesh=None) for l in plan.layers]
    assert got.mesh == MESH_SPEC
    assert all(l.mesh is not None for l in got.layers)
    payload = json.load(open(v4))
    assert payload["version"] == PLAN_CACHE_VERSION and payload["mesh"] is not None


def test_plan_matches_rejects_other_mesh():
    gemms, plan = _tuned_plan()
    other = MeshSpec(axes=(("data", 1), ("model", 8)), dp_axes=("data",))
    assert plan_matches(plan, gemms, mesh=MESH_SPEC)
    assert not plan_matches(plan, gemms, mesh=other)
    # a mesh-tuned plan still serves a single-device request
    assert plan_matches(plan, gemms)


class _FakeMesh:
    def __init__(self, shape: dict):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_dp_size_single_definition():
    """The canonical launch.mesh.dp_size and the rules-context wrapper
    models.sharding.dp_size agree on the production meshes."""
    from repro.launch.mesh import dp_axes, dp_size
    from repro.models import sharding

    for shape in ({"data": 16, "model": 16},
                  {"pod": 2, "data": 16, "model": 16},
                  {"data": 4, "model": 2}):
        mesh = _FakeMesh(shape)
        with sharding.use_rules(mesh):
            assert sharding.dp_size() == dp_size(mesh)
        assert dp_size(mesh) == dp_size(mesh, dp_axes(mesh))
    assert sharding.dp_size() == 1  # outside any rules context


# ---------------------------------------------------------------------------
# multi-device: the shard_map-composed kernels
# ---------------------------------------------------------------------------


def _mesh24():
    from repro.launch.mesh import make_mesh

    return make_mesh((2, 4), ("data", "model"))


def _linear_case(M=64, K=32, N=48, bias=True, residual=True):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (M, K), jnp.float32)
    w = jax.random.normal(ks[1], (K, N), jnp.float32) * 0.1
    b = jax.random.normal(ks[2], (N,), jnp.float32) if bias else None
    r = jax.random.normal(ks[3], (M, N), jnp.float32) if residual else None
    return x, w, b, r


@multi_device
@pytest.mark.parametrize("mesh_df", [Dataflow.WS, Dataflow.IS, Dataflow.OS])
@pytest.mark.parametrize("epilogue", [(None, False, False), ("gelu", True, True)])
def test_sharded_matches_reference_fwd_and_grad(mesh_df, epilogue):
    """Acceptance: each mesh dataflow == the XLA/GSPMD reference to f32
    tolerance, forward and jax.grad."""
    from repro.core.cmu import GemmPlan, MeshPlan
    from repro.kernels import linear_ref
    from repro.kernels.mesh_ops import flex_linear_sharded

    activation, bias, residual = epilogue
    x, w, b, r = _linear_case(bias=bias, residual=residual)
    mesh = _mesh24()
    plan = MeshPlan(dataflow=mesh_df, axis="model", tp=4, dp=2,
                    local=GemmPlan(dataflow=Dataflow.OS, block=(64, 64, 64),
                                   est_cost=0.0))

    def f(x, w, b, r):
        return flex_linear_sharded(
            x, w, b, mesh=mesh, axis="model", dp_axes=("data",),
            activation=activation, residual=r, plan=plan, interpret=True,
        )

    ref = linear_ref(x, w, b, activation=activation, residual=r)
    out = jax.jit(f)(x, w, b, r)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)

    argnums = (0, 1) + ((2,) if bias else ()) + ((3,) if residual else ())
    g = jax.grad(lambda *a: (f(*a) ** 2).sum(), argnums=argnums)(x, w, b, r)
    g_ref = jax.grad(
        lambda *a: (linear_ref(a[0], a[1], a[2], activation=activation,
                               residual=a[3]) ** 2).sum(),
        argnums=argnums,
    )(x, w, b, r)
    for got, want in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-4, rtol=2e-4)


@multi_device
def test_sharded_trace_time_fallback_plan_none():
    """plan=None picks the mesh dataflow from the analytical ICI model at
    trace time — same numbers, no plan required."""
    from repro.kernels import linear_ref
    from repro.kernels.mesh_ops import flex_linear_sharded

    x, w, b, r = _linear_case()
    out = flex_linear_sharded(
        x, w, b, mesh=_mesh24(), axis="model", dp_axes=("data",),
        activation="relu", residual=r, plan=None, interpret=True,
    )
    ref = linear_ref(x, w, b, activation="relu", residual=r)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@multi_device
def test_layers_linear_routes_mesh_native_and_falls_back():
    """models.layers.linear under a rules context matches the single-device
    kernel path; a non-dividing GEMM falls back cleanly (attention-path
    contract)."""
    from repro.models.config import ModelConfig
    from repro.models.layers import linear
    from repro.models.sharding import use_rules

    cfg = ModelConfig(use_pallas=True, dtype="float32")
    mesh = _mesh24()
    kx, kw = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(kx, (2, 16, 64), jnp.float32)   # M = 32 divides 8
    w = jax.random.normal(kw, (64, 128), jnp.float32) * 0.1
    ref = linear(cfg, x, w, activation="silu", name="mlp.w1")
    with use_rules(mesh):
        out = jax.jit(lambda x: linear(cfg, x, w, activation="silu",
                                       name="mlp.w1"))(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)

    # gradient through the routed path
    loss = lambda w: (linear(cfg, x, w, activation="silu", name="mlp.w1") ** 2).mean()
    g_ref = jax.grad(loss)(w)
    with use_rules(mesh):
        g = jax.jit(jax.grad(loss))(w)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               atol=2e-4, rtol=2e-4)

    # ragged K: 62 % 4 != 0 -> single-device fallback, same numbers
    w_r = jax.random.normal(kw, (62, 128), jnp.float32) * 0.1
    x_r = jax.random.normal(kx, (2, 16, 62), jnp.float32)
    ref_r = linear(cfg, x_r, w_r, name="mlp.w1")
    with use_rules(mesh):
        out_r = linear(cfg, x_r, w_r, name="mlp.w1")
    np.testing.assert_allclose(np.asarray(out_r), np.asarray(ref_r),
                               atol=1e-5, rtol=1e-5)


@multi_device
def test_layers_linear_uses_planned_mesh_subplan():
    """An activated plan's mesh sub-plan drives the routed dispatch."""
    from repro.models.config import ModelConfig
    from repro.models.layers import linear
    from repro.models.sharding import use_rules

    gemms = [GemmShape(32, 64, 128, name="mlp.w1")]
    spec = MeshSpec(axes=(("data", 2), ("model", 4)), dp_axes=("data",))
    plan = autotune_plan(gemms, mesh=spec, **TUNE_KW)
    assert plan.layers[0].mesh is not None
    cfg = ModelConfig(use_pallas=True, dtype="float32")
    kx, kw = jax.random.split(jax.random.PRNGKey(2))
    x = jax.random.normal(kx, (2, 16, 64), jnp.float32)
    w = jax.random.normal(kw, (64, 128), jnp.float32) * 0.1
    ref = linear(cfg, x, w, name="mlp.w1")
    activate_plan(plan)
    try:
        with use_rules(_mesh24()):
            out = jax.jit(lambda x: linear(cfg, x, w, name="mlp.w1"))(x)
    finally:
        activate_plan(None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@multi_device
def test_constrain_warns_once_on_involuntary_replication(caplog):
    """An axis whose dim doesn't divide the mesh extent is replicated with
    one warning per (axis, shape) site — visible in logs, not silent."""
    from repro.models import sharding

    mesh = _mesh24()
    x = jnp.zeros((2, 6, 8))  # 6 % 4 != 0 on the model axis
    sharding._REPLICATION_WARNED.clear()
    with sharding.use_rules(mesh):
        with caplog.at_level(logging.WARNING, logger="repro.models.sharding"):
            sharding.constrain(x, "act_batch", "act_seq", None)
            warned = [r for r in caplog.records if "act_seq" in r.message]
            assert len(warned) == 1
            assert "replicating" in warned[0].message
            # second identical call: no new warning (once per site)
            sharding.constrain(x, "act_batch", "act_seq", None)
            assert len([r for r in caplog.records
                        if "act_seq" in r.message]) == 1
            # a different shape is a different site
            sharding.constrain(jnp.zeros((2, 10, 8)), "act_batch", "act_seq",
                               None)
            assert len([r for r in caplog.records
                        if "act_seq" in r.message]) == 2
