"""jit'd public wrappers around the flex dataflow kernels.

``flex_linear`` is the op the model stack calls: a full linear layer —
``act(x @ w + b) + residual`` — with the epilogue fused into the Pallas
kernel's final flush, so bias/activation/residual never re-stream the matmul
output through HBM.  It pads to block multiples, dispatches to the
CMU-selected dataflow kernel, and unpads.

``flex_matmul`` is the bare-matmul variant kept for benchmarks and the
paper-claims suite; ``auto_matmul`` adds trace-time CMU dataflow selection.
The model stack falls back to plain XLA einsum when the kernel path is
disabled (CPU dry-runs / compile-only meshes, where XLA must see a fusible
dot for cost_analysis).

**Training (custom VJP).**  Both ops carry a ``jax.custom_vjp`` so
``jax.grad`` keeps the hot path on Pallas: the two backward GEMMs

  dX[M,K] = dY[M,N] @ W^T[N,K]        (cotangent wrt activations)
  dW[K,N] = X^T[K,M] @ dY[M,N]        (cotangent wrt weights)

run as flex kernels under their **own** (dataflow, block) — the backward
shapes generally prefer different stationarity than the forward (the paper's
per-layer reconfiguration argument applied to training).  ``flex_linear``
takes ``bwd_dx`` / ``bwd_dw`` overrides from a CMU train plan (None means
the trace-time roofline argmin); ``flex_matmul``'s backward always uses the
trace-time argmin.

**Transpose-free backward (default).**  The operand transposes above are
expressed through the kernels' ``trans_a`` / ``trans_b`` index-map variants:
dX streams W exactly as stored, (K, N) physical read as (N, K)-logical
(``trans_b``), and dW streams X as stored, (M, K) physical read as
(K, M)-logical (``trans_a``) — **no HBM transpose copy is ever issued**.  A
``BwdSpec`` may carry an explicit third element ``(trans_a, trans_b)``; a
CMU plan that *measured* the copy-based fallback as faster (it rarely is —
the copy round-trips the operand through HBM) can program
``(False, False)``, in which case the transpose is materialised exactly as
the pre-v3 code did.

Residual policy: **save, don't recompute**.  The forward kernel emits the
f32 pre-activation ``z = x @ w + b`` as a second output (``save_preact``) —
free for WS/IS whose staging buffer already materialises it, one extra f32
write for OS — and the VJP differentiates the epilogue as

  d_residual = dY
  dZ         = dY * act'(z)           (via jax.vjp of the activation at z)
  d_bias     = sum_M dZ

Saving z costs M*N*4 bytes of HBM versus recomputing the full forward GEMM
in the backward pass; on every shape the CMU models, the write is cheaper.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.dataflow import (
    Dataflow,
    GemmShape,
    best_kernel_dataflow,
    revisits_output,
)

from . import flex_matmul as fk
from .quantize import QDTYPES, quantize_channel

# Override for one backward GEMM, e.g. from a CMU plan:
#   (Dataflow.WS, (256, 256, 256))                 — block None = DEFAULT_BLOCK
#   (Dataflow.WS, (256, 256, 256), (False, True))  — explicit operand layout:
#     the third element is (trans_a, trans_b); omitted means the role's
#     zero-copy transposed-operand variant (the v3 default).
#   (Dataflow.WS, (256, 256, 256), (False, True), 4) — explicit accumulator
#     strip depth; omitted (pre-v4 specs) means 1, today's streamed WS/IS.
BwdSpec = tuple  # (Dataflow, block | None[, (trans_a, trans_b)[, strip]])


def _pad_to(x: jax.Array, m0: int, m1: int) -> jax.Array:
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def default_interpret() -> bool:
    """Pallas kernels need interpret mode off-TPU (CPU CI, dry-runs)."""
    return jax.default_backend() != "tpu"


def _fit_block(M: int, K: int, N: int, block: tuple[int, int, int]):
    """Shrink each block dim to the padded extent of its GEMM dim — a block
    larger than the (128-aligned) dim just wastes VMEM — while honouring
    CMU-tuned blocks above 128."""

    def fit(d: int, bd: int) -> int:
        return min(bd, _round_up_dim(d))

    bm, bk, bn = block
    return fit(M, bm), fit(K, bk), fit(N, bn)


def _round_up_dim(d: int, mult: int = 128) -> int:
    """Smallest MXU-aligned extent covering d (min 8 sublanes for tiny dims)."""
    if d >= mult:
        return -(-d // mult) * mult
    r = 8
    while r < d:
        r *= 2
    return r


def _fit_strip(dataflow: Dataflow, strip: int, M: int, N: int,
               block: tuple[int, int, int]) -> int:
    """Clamp a requested accumulator-strip depth to what the padded geometry
    admits: the largest depth <= ``strip`` that tiles the strip axis's block
    count exactly (M blocks for WS, N blocks for IS).  OS always runs 1.
    CMU-planned strips already tile the axis they were tuned for, so this
    only engages when a plan is applied to a different (padded) geometry.
    """
    if strip <= 1 or dataflow is Dataflow.OS:
        return 1
    bm, _, bn = block
    # the padded extent is the next block multiple, so ceil is the block count
    blocks = -(-M // bm) if dataflow is Dataflow.WS else -(-N // bn)
    s = max(1, min(strip, blocks))
    while blocks % s:
        s -= 1
    return s


def _fit_schedule(dataflow: Dataflow, strip: int, M: int, K: int, N: int,
                  block: tuple[int, int, int], interpret: bool):
    """The (dataflow, block, strip) a GEMM dispatches: the block fitted to
    the padded dims (``_fit_block``), the strip clamped to what they admit
    (``_fit_strip``).  Compiled for the chip, a WS/IS schedule left with a
    strip of 1 and more than one k block — a trace-time fallback, or a plan
    tuned at a longer M applied to a shorter one — runs as OS at the same
    block: Mosaic never reads a revisited partial-sum block back
    (``dataflow.revisits_output``), and OS keeps the block's accumulator in
    VMEM across k.  Every dataflow computes the same bits."""
    blk = _fit_block(M, K, N, block)
    strip = _fit_strip(dataflow, strip, M, N, blk)
    if not interpret and revisits_output(dataflow, K, blk[1], strip):
        dataflow = Dataflow.OS
    return dataflow, blk, strip


def _bwd_choice(spec: BwdSpec | None, M: int, K: int, N: int,
                default_trans: tuple[bool, bool] = (False, False)):
    """Resolve one backward GEMM's (dataflow, block, trans, strip): the CMU
    plan's choice when given, else the trace-time roofline argmin (shapes
    are static).  ``default_trans`` is the role's zero-copy operand layout —
    a 2-tuple spec (legacy, pre-v3) inherits it; a 3-tuple spec states its
    own (the CMU may have measured the copy-based fallback as faster).  The
    optional 4th element is the accumulator-strip depth; pre-v4 specs omit
    it and run streamed (strip=1), as does the trace-time fallback."""
    if spec is not None:
        df, blk = spec[0], spec[1]
        trans = tuple(spec[2]) if len(spec) > 2 and spec[2] is not None \
            else default_trans
        strip = int(spec[3]) if len(spec) > 3 and spec[3] else 1
        return df, tuple(blk) if blk else fk.DEFAULT_BLOCK, trans, strip
    df, _ = best_kernel_dataflow(GemmShape(M=M, K=K, N=N))
    return df, fk.DEFAULT_BLOCK, default_trans, 1


# ---------------------------------------------------------------------------
# flex_matmul — bare matmul with a flex-kernel VJP
# ---------------------------------------------------------------------------


def _matmul_run(a, b, dataflow, block, interpret, out_dtype,
                trans_a: bool = False, trans_b: bool = False, strip: int = 1,
                qdtype: str | None = None):
    """Primal blocked matmul: pad -> flex kernel -> unpad -> cast.

    With ``trans_a`` / ``trans_b`` the operands are in transposed physical
    layout ((K, M) / (N, K)); padding follows the physical axes and the
    kernel reads them through the transposed index maps — no copy.
    ``strip`` selects the WS/IS two-level schedule, clamped to what the
    padded geometry admits (``_fit_strip``).  ``qdtype`` quantizes the B
    operand per output channel (int8/fp8) and dispatches the fused-dequant
    kernel — untransposed operands only (the backward GEMMs run on the
    saved full-precision operands, so the quant path never needs trans).
    """
    M, K, N = fk._logical_dims(a, b, trans_a, trans_b)
    dataflow, (bm, bk, bn), strip = _fit_schedule(dataflow, strip, M, K, N,
                                                  block, interpret)
    if qdtype in QDTYPES:
        if trans_a or trans_b:
            raise ValueError(
                "quantized flex_matmul supports untransposed operands only")
        out_dtype = out_dtype or jnp.promote_types(a.dtype, b.dtype)
        qb, scale = quantize_channel(b, qdtype, axis=0)
        out = fk.fused_matmul(
            _pad_to(a, bm, bk), _pad_to(qb, bk, bn), dataflow,
            qscale=_pad_to(scale, 1, bn), block=(bm, bk, bn),
            interpret=interpret, strip=strip,
        )
        return out[:M, :N].astype(out_dtype)
    ap = _pad_to(a, bk, bm) if trans_a else _pad_to(a, bm, bk)
    bp = _pad_to(b, bn, bk) if trans_b else _pad_to(b, bk, bn)
    out = fk.matmul(ap, bp, dataflow, block=(bm, bk, bn), interpret=interpret,
                    trans_a=trans_a, trans_b=trans_b, strip=strip)
    out = out[:M, :N]
    return out.astype(out_dtype or jnp.promote_types(a.dtype, b.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _matmul_core(cfg, a, b):
    return _matmul_run(a, b, *cfg)


def _matmul_fwd(cfg, a, b):
    return _matmul_core(cfg, a, b), (a, b)


def _matmul_bwd(cfg, residuals, g):
    # qdtype is forward-only: the cotangent GEMMs run on the saved
    # full-precision operands (straight-through estimator).
    dataflow, block, interpret, out_dtype, trans_a, trans_b, strip, _ = cfg
    a, b = residuals
    M, K, N = fk._logical_dims(a, b, trans_a, trans_b)
    # With A' = op(A), B' = op(B):  dA' = g @ B'^T  and  dB' = A'^T @ g.
    # Each cotangent is issued directly in its operand's *stored* layout —
    # the trans flags below are the algebra of op() folded into the index
    # maps, so no combination of flags ever materialises a transpose.
    if trans_a:
        # dA (stored (K, M)) = B' @ g^T — a (K,N)x(N,M) GEMM.
        df, blk, _, st = _bwd_choice(None, K, N, M)
        da = _matmul_run(b, g, df, blk, interpret, a.dtype,
                         trans_a=trans_b, trans_b=True, strip=st)
    else:
        # dA = g @ B'^T — an (M,N)x(N,K) GEMM; B'^T reads stored B directly.
        df, blk, _, st = _bwd_choice(None, M, N, K)
        da = _matmul_run(g, b, df, blk, interpret, a.dtype,
                         trans_b=not trans_b, strip=st)
    if trans_b:
        # dB (stored (N, K)) = g^T @ A' — an (N,M)x(M,K) GEMM.
        df, blk, _, st = _bwd_choice(None, N, M, K)
        db = _matmul_run(g, a, df, blk, interpret, b.dtype,
                         trans_a=True, trans_b=trans_a, strip=st)
    else:
        # dB = A'^T @ g — a (K,M)x(M,N) GEMM; A'^T reads stored A directly.
        df, blk, _, st = _bwd_choice(None, K, M, N)
        db = _matmul_run(a, g, df, blk, interpret, b.dtype,
                         trans_a=not trans_a, strip=st)
    return da, db


_matmul_core.defvjp(_matmul_fwd, _matmul_bwd)


@functools.partial(
    jax.jit, static_argnames=("dataflow", "block", "interpret", "out_dtype",
                              "trans_a", "trans_b", "strip", "qdtype")
)
def flex_matmul(
    a: jax.Array,
    b: jax.Array,
    dataflow: Dataflow = Dataflow.OS,
    block: tuple[int, int, int] = fk.DEFAULT_BLOCK,
    interpret: bool = False,
    out_dtype: jnp.dtype | None = None,
    trans_a: bool = False,
    trans_b: bool = False,
    strip: int = 1,
    qdtype: str | None = None,
) -> jax.Array:
    """C = op(A) @ op(B) under the given dataflow; pads/unpads to block
    multiples.  ``trans_a`` / ``trans_b`` read the operands in transposed
    physical layout through the kernels' index maps — zero HBM copies.
    ``strip >= 2`` runs the WS/IS two-level schedule (VMEM-resident
    accumulator strip, no partial-sum HBM traffic), clamped to the padded
    geometry; OS and ``strip = 1`` run today's streamed schedules.
    ``qdtype`` ("int8"/"fp8") quantizes B per output channel and runs the
    fused-dequant kernel — forward only; gradients flow straight-through.

    Differentiable: ``jax.grad`` routes both cotangent GEMMs back through
    the flex kernels, themselves transpose-free for every flag combination
    (see the module docstring's VJP contract).
    """
    fk._logical_dims(a, b, trans_a, trans_b)  # validates the inner dims
    return _matmul_core(
        (dataflow, block, interpret, out_dtype, trans_a, trans_b, strip,
         qdtype), a, b
    )


# ---------------------------------------------------------------------------
# flex_linear — fused linear layer with a flex-kernel VJP
# ---------------------------------------------------------------------------


class _LinearCfg(NamedTuple):
    """Hashable trace-time config for one fused linear (the nondiff arg)."""

    activation: str | None
    dataflow: Dataflow
    block: tuple[int, int, int]
    interpret: bool
    out_dtype: jnp.dtype | None
    bwd_dx: BwdSpec | None
    bwd_dw: BwdSpec | None
    strip: int = 1
    qdtype: str | None = None


def _linear_run(cfg: _LinearCfg, x, w, b, residual, save_preact: bool):
    """Primal fused linear; returns (out, z) with z=None unless save_preact."""
    M, K = x.shape
    _, N = w.shape
    dataflow, (bm, bk, bn), strip = _fit_schedule(
        cfg.dataflow, cfg.strip, M, K, N, cfg.block, cfg.interpret)
    odt = cfg.out_dtype or jnp.promote_types(x.dtype, w.dtype)
    qscale = None
    if cfg.qdtype in QDTYPES:
        # weight-only quant: per-output-channel scale rides the bias plumbing
        # into the kernel, dequant fuses at the flush before the epilogue
        w, qscale = quantize_channel(w, cfg.qdtype, axis=0)
        qscale = _pad_to(qscale, 1, bn)
    xp = _pad_to(x, bm, bk)
    wp = _pad_to(w, bk, bn)
    bp = None if b is None else _pad_to(b.reshape(1, N), 1, bn)
    rp = None if residual is None else _pad_to(residual, bm, bn)
    # the dispatched dataflow names the kernel's scope ("is"/"os"/"ws")
    with jax.named_scope(dataflow.name.lower()):
        out = fk.fused_matmul(
            xp, wp, dataflow,
            bias=bp, residual=rp, activation=cfg.activation, out_dtype=odt,
            block=(bm, bk, bn), interpret=cfg.interpret, save_preact=save_preact,
            strip=strip, qscale=qscale,
        )
    if save_preact:
        out, z = out
        return out[:M, :N].astype(odt), z[:M, :N]
    return out[:M, :N].astype(odt), None


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _linear_core(cfg: _LinearCfg, x, w, b, residual):
    out, _ = _linear_run(cfg, x, w, b, residual, save_preact=False)
    return out


def _linear_fwd(cfg: _LinearCfg, x, w, b, residual):
    # z is only needed to differentiate the activation; bias/residual grads
    # come straight from the cotangent.  Zero-size protos carry the epilogue
    # operands' dtypes to bwd without retaining the arrays.
    need_z = cfg.activation is not None
    out, z = _linear_run(cfg, x, w, b, residual, save_preact=need_z)
    # zero-size protos keep b/residual's shape rank and dtype for bwd (the
    # cotangent aval must match the primal: (N,) vs (1, N) bias both work)
    b_proto = None if b is None else jnp.zeros((0,) * b.ndim, b.dtype)
    r_proto = None if residual is None else jnp.zeros((0,), residual.dtype)
    return out, (x, w, b_proto, r_proto, z)


def _linear_bwd(cfg: _LinearCfg, residuals, g):
    x, w, b_proto, r_proto, z = residuals
    M, K = x.shape
    N = w.shape[1]
    g32 = g.astype(jnp.float32)
    if cfg.activation is not None:
        # exact activation derivative at the saved pre-activation
        _, act_vjp = jax.vjp(fk.ACTIVATIONS[cfg.activation], z)
        dz = act_vjp(g32)[0]
    else:
        dz = g32
    # The two backward GEMMs, each under its own CMU-planned (dataflow,
    # block, operand layout, strip).  Default layouts are the zero-copy
    # variants: dX streams W as stored via trans_b, dW streams X as stored
    # via trans_a.  A plan that measured the copy-based fallback as faster
    # programs (False, False) and the transpose is materialised explicitly.
    df_dx, blk_dx, tr_dx, st_dx = _bwd_choice(cfg.bwd_dx, M, N, K, (False, True))
    df_dw, blk_dw, tr_dw, st_dw = _bwd_choice(cfg.bwd_dw, K, M, N, (True, False))
    gd = dz.astype(jnp.promote_types(x.dtype, w.dtype))
    dx = _matmul_run(gd, w if tr_dx[1] else w.T, df_dx, blk_dx,
                     cfg.interpret, x.dtype, trans_b=tr_dx[1], strip=st_dx)
    dw = _matmul_run(x if tr_dw[0] else x.T, gd, df_dw, blk_dw,
                     cfg.interpret, w.dtype, trans_a=tr_dw[0], strip=st_dw)
    if b_proto is None:
        db = None
    else:
        db = dz.sum(axis=0, keepdims=b_proto.ndim == 2).astype(b_proto.dtype)
    dr = None if r_proto is None else g.astype(r_proto.dtype)
    return dx, dw, db, dr


_linear_core.defvjp(_linear_fwd, _linear_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("activation", "dataflow", "block", "interpret",
                     "out_dtype", "bwd_dx", "bwd_dw", "strip", "qdtype"),
)
def flex_linear(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array | None = None,
    *,
    activation: str | None = None,
    residual: jax.Array | None = None,
    dataflow: Dataflow = Dataflow.OS,
    block: tuple[int, int, int] = fk.DEFAULT_BLOCK,
    interpret: bool = False,
    out_dtype: jnp.dtype | None = None,
    bwd_dx: BwdSpec | None = None,
    bwd_dw: BwdSpec | None = None,
    strip: int = 1,
    qdtype: str | None = None,
) -> jax.Array:
    """Fused linear layer: ``act(x @ w + b) + residual`` in one kernel pass.

    x (M, K); w (K, N); b (N,) or None; residual (M, N) or None;
    ``activation`` in {relu, gelu, silu, None}.  Bias/activation/residual and
    the output cast all run inside the kernel's final flush while the f32
    accumulator block is resident in VMEM — no extra HBM round-trips.
    Pads/unpads to block multiples (zero padding is epilogue-safe: the padded
    rows/cols are sliced off before any consumer sees them).

    Differentiable end-to-end: under ``jax.grad`` the backward GEMMs
    ``dX = dY @ W^T`` and ``dW = X^T @ dY`` run as flex kernels under
    ``bwd_dx`` / ``bwd_dw`` — ``(Dataflow, (bm, bk, bn), (trans_a,
    trans_b), strip)`` tuples, normally supplied by the CMU train plan — or
    the trace-time roofline argmin when None.  The third element is the
    operand layout: omitted (legacy 2-tuples) or the role's default means
    the zero-copy transposed-operand kernel that streams W/X as stored;
    ``(False, False)`` forces the copy-based fallback that materialises the
    transpose in HBM first.  The fourth element is the accumulator-strip
    depth (omitted = 1, streamed).  ``strip`` plays the same role for the
    forward GEMM.  The activation gradient uses the pre-activation the
    forward kernel saved (see module docstring for the save-vs-recompute
    policy).

    ``qdtype`` ("int8"/"fp8") runs the forward GEMM with the weight
    quantized per output channel, dequant fused into the flush before
    bias/activation/residual/cast.  Forward-only: the VJP saves the
    full-precision weight and both cotangent GEMMs run unquantized
    (straight-through estimator), so training against a quantized serve
    plan needs no extra plumbing.

    Examples (interpret mode, so they run anywhere):

    >>> import jax, jax.numpy as jnp
    >>> from repro.kernels import flex_linear
    >>> x = jnp.ones((8, 16)); w = jnp.full((16, 8), 0.1)
    >>> flex_linear(x, w, activation="relu", interpret=True).shape
    (8, 8)
    >>> dx = jax.grad(lambda x: flex_linear(x, w, interpret=True).sum())(x)
    >>> round(float(dx[0, 0]), 4)   # d/dx sum(x @ w) = sum_N w = 0.8
    0.8
    """
    M, K = x.shape
    K2, N = w.shape
    if K != K2:
        raise ValueError(f"inner dims mismatch: {x.shape} @ {w.shape}")
    cfg = _LinearCfg(activation, dataflow, block, interpret, out_dtype,
                     bwd_dx, bwd_dw, strip, qdtype)
    return _linear_core(cfg, x, w, b, residual)


def auto_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    name: str = "",
    interpret: bool = False,
) -> jax.Array:
    """CMU-in-the-loop matmul: picks the dataflow from shapes at trace time.

    Shape-driven and trace-time static — the deployment model of the paper
    (offline selection, zero runtime switching cost).
    """
    shape = GemmShape(M=a.shape[0], K=a.shape[1], N=b.shape[1], name=name)
    df, _ = best_kernel_dataflow(shape)
    return flex_matmul(a, b, dataflow=df, interpret=interpret)
