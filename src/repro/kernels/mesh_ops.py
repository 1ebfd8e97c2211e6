"""Mesh-native flex kernels: shard_map-composed distributed GEMM schedules.

This module resolves the kernel-vs-GSPMD composition question (ROADMAP,
carried since PR 2) in favour of **explicit shard_map composition**: the
collective schedule around each layer's GEMM is chosen per layer by the
mesh-level CMU (``core.dist_dataflow``), not left to GSPMD's solver, and
the local per-shard GEMM inside the shard_map is the same fused Pallas
flex kernel the single-device path runs — with its own chip-level
(dataflow, block, strip, trans) plan tuned for the *post-collective*
shard shapes.

The three mesh dataflows are the paper's three stationarities one level up
the hierarchy (chip <-> PE, ICI <-> systolic wiring).  For a global
``C[M,N] = A[M,K] @ B[K,N]`` with tokens sharded over ``(*dp_axes, axis)``
and the weight K-sharded over ``axis`` (extent T):

  mesh-WS   the weight shards never move.  A is all-gathered over ``axis``
            (rebuilding the DP group's token block), each chip contracts
            its own K-shard — a bare local flex kernel producing an (M/dp,
            N) f32 partial — and a psum-scatter over ``axis`` both reduces
            the partials and re-shards the tokens.  The epilogue applies
            *after* the reduction (bias must be added once, the activation
            is nonlinear), as plain f32 ops on the scattered shard.
  mesh-IS   the activations never move.  The weight shard is all-gathered
            (ZeRO-3 style) and the local kernel runs the **whole** layer —
            the only mesh dataflow whose fused epilogue stays in-kernel.
  mesh-OS   nothing is gathered.  Each chip's output shard stays resident
            while the weight shard rotates around the ring
            (collective-permute), one local kernel launch per rotation
            step, f32 partials accumulating locally; A's matching k-slices
            are already local because the token shard carries full K.
            Epilogue after the last step, like WS.

All three share one I/O contract: x, residual and the output are sharded
``P((*dp_axes, axis), None)`` (tokens over the whole grid), the weight
``P(axis, None)`` (K over the tensor axis, replicated over DP — the ZeRO-3
unshard from the stored ``fsdp`` sharding is delegated to GSPMD at the
shard_map boundary), bias replicated.  Data-parallel axes never appear in
a collective: each DP group runs the schedule independently.

Everything is differentiable end-to-end: the collectives' transposes
(all-gather <-> psum-scatter, collective-permute <-> reverse permute) are
jax built-ins, and the local GEMMs carry the flex kernels' custom VJPs, so
under ``jax.grad`` the backward GEMMs run as flex kernels under the mesh
sub-plan's ``local_dx`` / ``local_dw`` geometries while the backward
collectives are exactly the forward schedule's transposes (mesh-WS
backward all-gathers the output cotangent and psum-scatters dX — the WS
schedule run in reverse).

Partial sums cross the wire in f32 (the ICI analogue of the kernels'
f32-accumulate policy); only the final epilogue casts to ``out_dtype``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.cmu import MeshPlan, mesh_local_gemm
from repro.core.dataflow import Dataflow, GemmShape, best_kernel_dataflow
from repro.core.dist_dataflow import best_mesh_dataflow
from repro.launch.mesh import dp_size

from . import flex_matmul as fk
from . import ops


def _local_specs(plan: MeshPlan | None, lshape: GemmShape):
    """Resolve the local kernel's (dataflow, block, strip) + backward
    BwdSpecs: from the mesh sub-plan when given, else the trace-time
    roofline argmin (backward then also falls to the trace-time argmin
    inside ``ops``)."""
    if plan is not None and plan.local is not None:
        lp = plan.local
        df, blk, strip = lp.dataflow, lp.block or fk.DEFAULT_BLOCK, lp.strip
    else:
        df, _ = best_kernel_dataflow(lshape)
        blk, strip = fk.DEFAULT_BLOCK, 1

    def bwd(sub):
        if sub is None:
            return None
        return (sub.dataflow, sub.block, sub.trans, sub.strip)

    return df, blk, strip, bwd(plan.local_dx if plan else None), \
        bwd(plan.local_dw if plan else None)


def _post_epilogue(c, b, res, activation: str | None, out_dtype):
    """bias -> activation -> residual -> cast on an f32 reduced shard —
    the same op order as the kernels' in-flush ``_epilogue``, applied
    post-reduction for the mesh dataflows whose partials must be summed
    before the (nonlinear, add-once) epilogue can run."""
    z = c if b is None else c + b.astype(jnp.float32)
    y = fk.ACTIVATIONS[activation](z) if activation is not None else z
    if res is not None:
        y = y + res.astype(jnp.float32)
    return y.astype(out_dtype)


def flex_linear_sharded(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array | None = None,
    *,
    mesh,
    axis: str,
    dp_axes: tuple[str, ...] = (),
    activation: str | None = None,
    residual: jax.Array | None = None,
    plan: MeshPlan | None = None,
    interpret: bool = False,
    out_dtype: jnp.dtype | None = None,
) -> jax.Array:
    """Distributed fused linear: ``act(x @ w + b) + residual`` as a
    shard_map-composed collective schedule around the local flex kernels.

    x (M, K) with M sharded over ``(*dp_axes, axis)``; w (K, N) K-sharded
    over ``axis``; b (N,) or None; residual (M, N) or None.  The output is
    (M, N), token-sharded like x.  Requires ``M % (dp * tp) == 0`` and
    ``K % tp == 0`` (``core.cmu.mesh_shardable`` — callers fall back to the
    single-device path otherwise, the same contract as the attention
    shard_map path).

    ``plan`` is the layer's CMU mesh sub-plan; None means trace-time
    selection: mesh dataflow from the analytical ICI model
    (``best_mesh_dataflow``), local geometry from the roofline argmin.
    Differentiable end-to-end (see module docstring).
    """
    M, K = x.shape
    K2, N = w.shape
    if K != K2:
        raise ValueError(f"inner dims mismatch: {x.shape} @ {w.shape}")
    tp = int(mesh.shape[axis])
    dp = dp_size(mesh, dp_axes)
    if tp <= 1 or M % (dp * tp) or K % tp:
        raise ValueError(
            f"GEMM ({M},{K},{N}) does not divide mesh (dp={dp}, tp={tp}); "
            "callers must fall back to the single-device path"
        )
    if plan is not None and (plan.tp != tp or plan.dp != dp
                             or plan.axis != axis):
        plan = None  # stale sub-plan (other topology): trace-time fallback
    if plan is not None:
        mesh_df = plan.dataflow
    else:
        mesh_df, _ = best_mesh_dataflow(GemmShape(M // dp, K, N), tp)
    lshape = mesh_local_gemm(GemmShape(M, K, N), mesh_df, tp, dp)
    ldf, lblk, lstrip, bwd_dx, bwd_dw = _local_specs(plan, lshape)
    odt = out_dtype or jnp.promote_types(x.dtype, w.dtype)
    ksh = K // tp

    def _is_body(x_l, w_l, b_l, r_l):
        # gather the K-sharded weight; the local kernel is the whole layer,
        # epilogue fused in the flush
        w_full = jax.lax.all_gather(w_l, axis, axis=0, tiled=True)
        return ops.flex_linear(
            x_l, w_full, b_l, activation=activation, residual=r_l,
            dataflow=ldf, block=lblk, interpret=interpret, out_dtype=odt,
            bwd_dx=bwd_dx, bwd_dw=bwd_dw, strip=lstrip,
        )

    def _ws_body(x_l, w_l, b_l, r_l):
        # rebuild the DP group's token block, contract this chip's K-shard,
        # reduce + re-shard the f32 partials in one psum-scatter
        a_full = jax.lax.all_gather(x_l, axis, axis=0, tiled=True)
        j = jax.lax.axis_index(axis)
        a_sl = jax.lax.dynamic_slice_in_dim(a_full, j * ksh, ksh, axis=1)
        part = ops.flex_linear(
            a_sl, w_l, None, dataflow=ldf, block=lblk, interpret=interpret,
            out_dtype=jnp.float32, bwd_dx=bwd_dx, bwd_dw=bwd_dw, strip=lstrip,
        )
        c = jax.lax.psum_scatter(part, axis, scatter_dimension=0, tiled=True)
        return _post_epilogue(c, b_l, r_l, activation, odt)

    def _os_body(x_l, w_l, b_l, r_l):
        # SUMMA ring: the output shard stays resident, the weight shard
        # rotates; step s contracts the k-slice matching the shard currently
        # held ((j + s) mod tp).  tp - 1 rotations, none after the last MAC.
        j = jax.lax.axis_index(axis)
        acc = jnp.zeros((x_l.shape[0], N), jnp.float32)
        w_cur = w_l
        for s in range(tp):
            src = (j + s) % tp
            a_sl = jax.lax.dynamic_slice_in_dim(x_l, src * ksh, ksh, axis=1)
            acc = acc + ops.flex_linear(
                a_sl, w_cur, None, dataflow=ldf, block=lblk,
                interpret=interpret, out_dtype=jnp.float32,
                bwd_dx=bwd_dx, bwd_dw=bwd_dw, strip=lstrip,
            )
            if s != tp - 1:
                w_cur = jax.lax.ppermute(
                    w_cur, axis, perm=[(i, (i - 1) % tp) for i in range(tp)]
                )
        return _post_epilogue(acc, b_l, r_l, activation, odt)

    body = {Dataflow.IS: _is_body, Dataflow.WS: _ws_body,
            Dataflow.OS: _os_body}[mesh_df]

    tok_spec = P((*dp_axes, axis), None)
    args, in_specs = [x, w], [tok_spec, P(axis, None)]
    if b is not None:
        args.append(b)
        in_specs.append(P(None))
    if residual is not None:
        args.append(residual)
        in_specs.append(tok_spec)

    def local_fn(*a):
        it = iter(a)
        x_l, w_l = next(it), next(it)
        b_l = next(it) if b is not None else None
        r_l = next(it) if residual is not None else None
        return body(x_l, w_l, b_l, r_l)

    return jax.shard_map(
        local_fn, mesh=mesh, in_specs=tuple(in_specs), out_specs=tok_spec,
        check_vma=False,
    )(*args)
