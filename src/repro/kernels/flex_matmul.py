"""Pallas TPU matmul kernels with reconfigurable dataflow (IS / OS / WS).

This is the TPU-native port of the Flex-TPU processing element (paper Fig. 3/4):
on a real TPU the programmable "stationarity" lives one level up the memory
hierarchy — which operand's VMEM block stays resident across consecutive grid
steps, determined by the grid loop order and each ``BlockSpec.index_map``:

  OS  grid (i, j, k):  the f32 accumulator block C[i,j] is pinned in VMEM
      scratch across the whole k loop and written to HBM exactly once.
  WS  grid (k, j, i):  the weight block B[k,j] is pinned across the entire
      M stream (its index_map ignores the innermost grid axis); partial sums
      stream through HBM (aliased read-modify-write) — the price WS pays when
      K exceeds one block, exactly as in `core.dataflow.hbm_traffic_bytes`.
  IS  grid (k, i, j):  symmetric — the activation block A[i,k] is pinned,
      weights stream, partials stream.

**Two-level stationarity (``strip`` >= 2).**  The streamed WS/IS schedules
above pay a cost the paper's hardware never would: every k step round-trips
the f32 output block through HBM.  With ``strip=ns`` the WS/IS kernels
instead pin a *strip* of ``ns`` output accumulator blocks in VMEM and
reorder the grid so each strip's k-revisits are consecutive:

  WS  grid (s, j, k, u), i = s*ns + u:  level 1 — the weight block B[k,j]
      stays pinned across the strip's inner M sweep (its index map ignores
      ``u``, exactly as the streamed schedule ignores ``i``); level 2 — the
      f32 accumulator strip stays pinned in VMEM across the whole k loop.
      Partial sums never touch HBM; each output block is written exactly
      once, like OS.  The price: B is re-fetched once per strip
      (``ceil(Mb/ns)`` times) instead of once.
  IS  grid (s, i, k, u), j = s*ns + u:  symmetric — the activation block
      A[i,k] is level-1 pinned across the strip's inner N sweep, the strip
      tiles N, and A is re-fetched once per strip.

``strip=1`` is exactly the streamed schedule.  OS takes no strip: its
accumulator is already VMEM-resident, and widening it to ``ns`` blocks
*is* the IS strip schedule (the search space already contains it).  The
strip grids' ``(s, j)`` / ``(s, i)`` axes are single-writer, so they are
declared ``"parallel"`` in ``dimension_semantics`` and megacore
partitioning can engage; the streamed grids stay all-``"arbitrary"``
(their output blocks are multi-writer across k).

All schedules compute bit-identical results (f32 accumulation in the same
k order); they differ only in HBM traffic and residency, which is the
paper's point.  The CMU (`core.cmu.autotune_plan`) picks the per-layer
``(dataflow, block, strip)`` offline; dispatch is static at trace time
(the JAX analogue of programming the CMU mux signals).
``schedule_cost_bytes`` walks the exact grids and index maps the builders
emit and counts HBM bytes under Pallas revisiting semantics — the guard
that keeps `core.dataflow.hbm_traffic_bytes` honest about what the
kernels actually do.

Every kernel supports a **fused epilogue** — bias add, activation
(relu/gelu/silu), residual add, and output dtype cast — applied inside the
kernel while the f32 accumulator block is still resident in VMEM:

  OS    the epilogue runs in the final-k ``_flush`` branch, so the epilogue
        reads the scratch accumulator and the single HBM write already
        carries the finished (possibly low-precision) result.
  WS/IS **strip >= 2**: the full epilogue (including the residual, fetched
        honestly once per strip — its index map ignores the k and u axes)
        runs off the VMEM-resident accumulator strip at flush.
  WS/IS **strip = 1** (streamed): bias/activation/cast run in a last-k-step
        branch off the f32 staging buffer; the *residual* add runs as one
        XLA op on the kernel's f32 output (same f32 op order, so results
        are bit-identical to the fused form).  An in-kernel residual fetch
        under the streamed grid would either re-stream the whole residual
        ``K/bk`` times or need an index-map workaround — the strip schedule
        is the honest fix, so the streamed path no longer fuses it.

Fusing the epilogue removes the extra HBM round-trips XLA would otherwise
spend re-streaming the matmul output through bias/activation/residual ops —
the on-chip-results argument of Jouppi et al. (2017) applied at VMEM level.

**Training support (fwd/bwd epilogue contract).**  With ``save_preact`` the
fused kernels additionally emit the f32 pre-activation ``z = a @ b + bias`` —
the residual ``ops.flex_linear``'s custom VJP needs to differentiate the
activation.  Streamed WS/IS get this for free: their f32 partial-sum staging
buffer already materialises ``a @ b`` in HBM, so the last-k flush just folds
the bias in and the staging buffer doubles as the saved pre-activation.
Strip WS/IS and OS pay one extra ``(M, N)`` f32 write from the flush — a
single clean write off the VMEM-resident accumulator, still far cheaper
than recomputing the forward GEMM in the backward pass.  The backward GEMMs
themselves (``dX = dY @ W^T``, ``dW = X^T @ dY``) are plain flex matmuls
issued by ``ops`` under their own CMU-planned (dataflow, block).

**Transposed operands (trans_a / trans_b).**  Every kernel accepts operands
in transposed physical layout: with ``trans_a`` the first operand is stored
``(K, M)`` and read as A^T, with ``trans_b`` the second is stored ``(N, K)``
and read as B^T.  The transpose lives entirely in the BlockSpec index map
(the block of logical ``A[i, k]`` is fetched from physical ``A[k, i]``) and
the in-kernel ``dot_general`` dimension numbers — **no HBM transpose copy is
ever issued**.  This is what lets the custom-VJP backward GEMMs
``dX = dY @ W^T`` and ``dW = X^T @ dY`` stream W and X exactly as stored:
dX streams W as (N,K)-logical, dW streams X as (K,M)-logical, zero copies.
Stationarity is unchanged — the pinned operand's index map still ignores
the innermost grid axis; only which physical axis maps to which grid index
swaps.

**Block-shape constraints.**  Every kernel requires the *logical* M, K, N to
be exact multiples of (bm, bk, bn); transposed operands are blocked with the
same (bm, bk, bn) applied to their physical axes — a ``trans_a`` operand is
blocked ``(bk, bm)``.  ``ops.flex_matmul`` / ``ops.flex_linear`` pad and
unpad around this.  Blocks should be MXU-aligned (multiples of 128, min 8
sublanes); ``DEFAULT_BLOCK`` is (256, 256, 256).  ``bias`` is (1, N) and
``residual`` (M, N), blocked (1, bn) / (bm, bn).

**Dtype / accumulator policy.**  Inputs may be any float dtype; every MAC
accumulates in f32 (``preferred_element_type=jnp.float32``), partial sums
stream through HBM in f32, the epilogue runs in f32, and only the final
flush casts to ``out_dtype``.  The saved pre-activation is always f32.

**Quantized operands (``qscale``).**  ``fused_matmul`` accepts a B operand
stored int8 or fp8(e4m3) with a per-output-channel f32 scale row
``qscale`` of shape (1, N), streamed alongside B with the bias's block
spec (one ``(1, bn)`` row per resident B block — epilogue-operand traffic,
like bias).  The MAC upcasts the quantized block to f32 (exact for int8
and e4m3 lattice points) and accumulates in f32 as always; because the
per-output-channel scale is constant across k, dequantization commutes
with the k-accumulation and runs **once at the flush epilogue**, before
everything else:

    dequant -> bias -> activation -> residual -> cast

so the existing epilogue contract — and the bit-exactness tests pinned on
it — compose unchanged.  The streamed WS/IS schedules force the fused
flush when ``qscale`` is present (the raw f32 staging buffer holds
*scaled-lattice* partial sums, which must not escape undequantized); the
saved pre-activation ``z`` is the dequantized ``a @ dequant(b) + bias``.

Kernels are written for TPU (MXU-aligned blocks, VMEM scratch) and validated
on CPU with ``interpret=True`` against ``ref.matmul_ref`` / ``ref.linear_ref``.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.dataflow import VMEM_BUDGET_BYTES, Dataflow, revisits_output

DEFAULT_BLOCK = (256, 256, 256)  # (bm, bk, bn) — MXU-aligned, ~768KB working set

_VMEM = pltpu.MemorySpace.VMEM


def compiler_params(*semantics: str) -> pltpu.CompilerParams:
    """Mosaic parameters shared by every flex kernel: the grid's dimension
    semantics, and ``VMEM_BUDGET_BYTES`` — the budget the CMU plans
    against — as the kernel's scoped-VMEM limit, so every schedule the
    feasibility model admits is one the compiler accepts."""
    return pltpu.CompilerParams(dimension_semantics=semantics or None,
                                vmem_limit_bytes=VMEM_BUDGET_BYTES)


# ---------------------------------------------------------------------------
# Fused epilogue
# ---------------------------------------------------------------------------

ACTIVATIONS: dict[str, Callable[[jax.Array], jax.Array]] = {
    "relu": lambda y: jnp.maximum(y, 0.0),
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
}


def _epilogue(acc, bias, res, activation: str | None):
    """bias -> activation -> residual, all on the resident f32 block.

    Takes *values* (already-sliced blocks), not refs, so the strip kernels
    can feed per-``u`` slices of their strip-wide bias/residual buffers.
    Returns ``(z, y)``: the pre-activation ``z = acc + bias`` (what the
    custom VJP saves to differentiate the activation) and the finished
    ``y = act(z) + residual``.
    """
    z = acc
    if bias is not None:
        z = z + bias.astype(jnp.float32)
    y = ACTIVATIONS[activation](z) if activation is not None else z
    if res is not None:
        y = y + res.astype(jnp.float32)
    return z, y


# ---------------------------------------------------------------------------
# Kernel bodies
# ---------------------------------------------------------------------------


def _block_dot(a, b, trans_a: bool, trans_b: bool):
    """One MAC on (possibly transposed-layout) operand blocks.

    The transpose is expressed purely in the contraction dimension numbers —
    a ``trans_a`` block is physically (bk, bm) and contracts axis 0, a
    ``trans_b`` block is (bn, bk) and contracts axis 1 — so the MXU consumes
    the block as stored and no relayout ever materialises.
    """
    dims = (((0 if trans_a else 1,), (1 if trans_b else 0,)), ((), ()))
    if a.dtype != b.dtype:
        # quantized path: B arrives int8/fp8 while A is a float dtype.
        # dot_general requires matching operand dtypes, so upcast both to the
        # f32 the MAC accumulates in anyway — exact for int8/e4m3 values.
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _os_kernel(*refs, activation: str | None, has_scale: bool = False,
               has_bias: bool, has_res: bool,
               save_preact: bool = False, trans_a: bool = False,
               trans_b: bool = False):
    """Output-stationary: accumulate in VMEM scratch across the k grid axis.

    The fused epilogue runs in the ``_flush`` branch — the accumulator block
    is still in VMEM, so bias/activation/residual cost zero extra HBM trips.
    With ``has_scale`` the flush first dequantizes the resident accumulator
    (``acc * qscale``, per output channel — exact, since the scale is
    constant across k) before the rest of the epilogue.
    With ``save_preact`` the flush also writes the f32 pre-activation block
    to a second output (the VJP's saved residual) — one extra HBM write.
    """
    it = iter(refs)
    a_ref, b_ref = next(it), next(it)
    scale_ref = next(it) if has_scale else None
    bias_ref = next(it) if has_bias else None
    res_ref = next(it) if has_res else None
    o_ref = next(it)
    z_ref = next(it) if save_preact else None
    acc_ref = next(it)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _block_dot(a_ref[...], b_ref[...], trans_a, trans_b)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        acc = acc_ref[...]
        if scale_ref is not None:
            acc = acc * scale_ref[...].astype(jnp.float32)
        z, y = _epilogue(
            acc,
            None if bias_ref is None else bias_ref[...],
            None if res_ref is None else res_ref[...],
            activation,
        )
        if save_preact:
            z_ref[...] = z
        o_ref[...] = y.astype(o_ref.dtype)


def _stream_accum_kernel(*refs, activation: str | None,
                         has_scale: bool = False, has_bias: bool,
                         fused: bool, save_preact: bool = False,
                         trans_a: bool = False, trans_b: bool = False):
    """WS/IS streamed (strip=1) body: one MAC into the HBM-streamed
    partial-sum block.

    The output block is revisited non-consecutively across the outer k axis,
    so partial sums stream through HBM (read-modify-write) — the structural
    price WS/IS pay when K exceeds one block, matching
    ``core.dataflow.hbm_traffic_bytes``.  The stationarity difference between
    WS and IS is entirely in the grid order and index_maps of the surrounding
    pallas_call (whose pinned operand ignores the innermost axis), not in the
    MAC itself — mirroring the paper's PE, where the same MAC hardware serves
    all three dataflows and only the mux selection changes.

    With ``fused`` the last-k-step branch applies bias/activation to the
    fully accumulated f32 partial block and writes the finished result once
    to a separate output buffer in the target dtype (partials must stay f32,
    so the low-precision final cast needs its own buffer).  The residual is
    *not* fused here — under the streamed grid its honest fetch would
    re-stream it every k plane, so ``_matmul_stream`` adds it outside the
    kernel in the same f32 op order; the strip kernels fuse it honestly.

    With ``save_preact`` the flush also folds the bias into the staging
    buffer, so after the kernel it holds the f32 pre-activation ``z`` — the
    VJP's saved residual at zero extra HBM cost (the buffer was being
    written every k step anyway).

    With ``has_scale`` the staging buffer accumulates scaled-lattice
    partial sums and the flush dequantizes before the epilogue — the driver
    forces ``fused`` on so the raw buffer never escapes undequantized.
    """
    it = iter(refs)
    a_ref, b_ref = next(it), next(it)
    scale_ref = next(it) if has_scale else None
    bias_ref = next(it) if has_bias else None
    part_ref = next(it)
    out_ref = next(it) if fused else None
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        part_ref[...] = jnp.zeros_like(part_ref)

    part_ref[...] += _block_dot(
        a_ref[...], b_ref[...], trans_a, trans_b
    ).astype(part_ref.dtype)

    if fused:

        @pl.when(k == pl.num_programs(0) - 1)
        def _flush():
            acc = part_ref[...]
            if scale_ref is not None:
                acc = acc * scale_ref[...].astype(jnp.float32)
            z, y = _epilogue(
                acc,
                None if bias_ref is None else bias_ref[...],
                None,
                activation,
            )
            if save_preact:
                part_ref[...] = z
            out_ref[...] = y.astype(out_ref.dtype)


def _strip_kernel(*refs, activation: str | None, has_scale: bool = False,
                  has_bias: bool, has_res: bool,
                  fused: bool, save_preact: bool, trans_a: bool, trans_b: bool,
                  ns: int, row_strip: bool):
    """WS/IS two-level body: one MAC into the VMEM-resident accumulator strip.

    The strip holds ``ns`` f32 output blocks — ``(ns*bm, bn)`` when the
    strip tiles M (WS), ``(bm, ns*bn)`` when it tiles N (IS).  Grid step
    ``(s, ·, k, u)`` MACs into the strip's ``u``-th slice; because the
    surrounding grid makes each strip's k-revisits consecutive, the strip
    buffer persists in VMEM across the whole k loop and partial sums never
    touch HBM.  The level-1 stationary operand (B for WS, A for IS) is
    pinned across the inner ``u`` sweep exactly as the streamed kernel pins
    it across its innermost axis.

    The flush at the last k step runs the **full** epilogue — including the
    residual, whose strip-wide block was fetched once per strip — and
    writes each finished block exactly once.  With ``save_preact`` the
    accumulator strip *is* the ``z`` output buffer (the bias folds in at
    flush), so the saved pre-activation costs one clean f32 write, never a
    partial-sum stream.
    """
    it = iter(refs)
    a_ref, b_ref = next(it), next(it)
    scale_ref = next(it) if has_scale else None
    bias_ref = next(it) if has_bias else None
    res_ref = next(it) if has_res else None
    o_ref = next(it)
    z_ref = next(it) if save_preact else None
    scratch_ref = next(it) if fused and not save_preact else None
    # accumulate into the z output when saving the pre-activation (it is the
    # staging buffer), else scratch (fused cast needs f32), else o_ref (f32)
    acc = z_ref if save_preact else (scratch_ref if fused else o_ref)
    k = pl.program_id(2)
    u = pl.program_id(3)
    if row_strip:  # strip tiles M: slice rows of the (ns*bm, bn) buffers
        bm = a_ref.shape[1] if trans_a else a_ref.shape[0]
        sl = (pl.ds(u * bm, bm), slice(None))
        blk_shape = (bm, acc.shape[1])
    else:  # strip tiles N: slice cols of the (bm, ns*bn) buffers
        bn = b_ref.shape[0] if trans_b else b_ref.shape[1]
        sl = (slice(None), pl.ds(u * bn, bn))
        blk_shape = (acc.shape[0], bn)

    @pl.when(k == 0)
    def _init():
        acc[sl] = jnp.zeros(blk_shape, acc.dtype)

    acc[sl] += _block_dot(a_ref[...], b_ref[...], trans_a, trans_b)

    if fused:

        @pl.when(k == pl.num_programs(2) - 1)
        def _flush():
            if bias_ref is None:
                bias = None
            else:  # WS bias block is (1, bn); IS carries (1, ns*bn), sliced
                bias = bias_ref[...] if row_strip else bias_ref[sl]
            blk = acc[sl]
            if scale_ref is not None:  # same layout as bias: dequant first
                scale = scale_ref[...] if row_strip else scale_ref[sl]
                blk = blk * scale.astype(jnp.float32)
            z, y = _epilogue(
                blk, bias,
                None if res_ref is None else res_ref[sl], activation,
            )
            if save_preact:
                z_ref[sl] = z
            o_ref[sl] = y.astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call builders (one per dataflow)
# ---------------------------------------------------------------------------


def _check(M: int, K: int, N: int, bm: int, bk: int, bn: int) -> None:
    if M % bm or K % bk or N % bn:
        raise ValueError(
            f"matmul dims ({M},{K},{N}) must divide blocks ({bm},{bk},{bn}); "
            "use ops.flex_matmul / ops.flex_linear which pad"
        )


def _logical_dims(a, b, trans_a: bool, trans_b: bool) -> tuple[int, int, int]:
    """(M, K, N) of ``op(a) @ op(b)`` given the physical operand layouts."""
    M, K = a.shape[::-1] if trans_a else a.shape
    K2, N = b.shape[::-1] if trans_b else b.shape
    if K != K2:
        raise ValueError(
            f"inner dims mismatch: {a.shape} @ {b.shape} "
            f"(trans_a={trans_a}, trans_b={trans_b})"
        )
    return M, K, N


def _operand_specs(bm, bk, bn, a_map, b_map, trans_a: bool, trans_b: bool):
    """BlockSpecs for A and B given *logical* index maps ``a_map`` (grid ids
    -> (i, k) block coords) and ``b_map`` (-> (k, j)).  A transposed operand
    gets the same logical map with its output pair swapped — the transpose
    lives in the index map, never in HBM."""
    if trans_a:
        a_spec = pl.BlockSpec((bk, bm), lambda *ids: a_map(*ids)[::-1])
    else:
        a_spec = pl.BlockSpec((bm, bk), a_map)
    if trans_b:
        b_spec = pl.BlockSpec((bn, bk), lambda *ids: b_map(*ids)[::-1])
    else:
        b_spec = pl.BlockSpec((bk, bn), b_map)
    return a_spec, b_spec


def _epilogue_inputs(qscale, bias, res, bias_map, out_map, bm, bn):
    """Extra (arrays, specs) for whichever epilogue operands are present.
    The quant scale row shares the bias's (1, bn) layout and index map."""
    arrays, specs = [], []
    if qscale is not None:
        arrays.append(qscale)
        specs.append(pl.BlockSpec((1, bn), bias_map))
    if bias is not None:
        arrays.append(bias)
        specs.append(pl.BlockSpec((1, bn), bias_map))
    if res is not None:
        arrays.append(res)
        specs.append(pl.BlockSpec((bm, bn), out_map))
    return arrays, specs


# ---------------------------------------------------------------------------
# Schedules: the (grid, index-map) tuples that *are* the dataflows.  Shared
# by the pallas_call builders and by ``schedule_cost_bytes``, so the traffic
# the cost model claims is counted off the very maps the kernels run.
# ---------------------------------------------------------------------------


def _os_schedule(mb: int, kb: int, nb: int):
    """OS grid (i, j, k): accumulator block pinned across the inner k loop."""
    grid = (mb, nb, kb)
    a_map = lambda i, j, k: (i, k)
    b_map = lambda i, j, k: (k, j)
    out_map = lambda i, j, k: (i, j)
    bias_map = lambda i, j, k: (0, j)
    return grid, a_map, b_map, out_map, bias_map


def _stream_schedule(stationary: str, mb: int, kb: int, nb: int):
    """Streamed (strip=1) WS/IS grids: k outermost, partials through HBM.
    The pinned operand's index map ignores the innermost grid axis."""
    if stationary == "weight":
        grid = (kb, nb, mb)  # WS: B[k,j] pinned across the inner M stream
        a_map = lambda k, j, i: (i, k)
        b_map = lambda k, j, i: (k, j)
        out_map = lambda k, j, i: (i, j)
        bias_map = lambda k, j, i: (0, j)
    elif stationary == "input":
        grid = (kb, mb, nb)  # IS: A[i,k] pinned across the inner N stream
        a_map = lambda k, i, j: (i, k)
        b_map = lambda k, i, j: (k, j)
        out_map = lambda k, i, j: (i, j)
        bias_map = lambda k, i, j: (0, j)
    else:  # pragma: no cover
        raise ValueError(stationary)
    return grid, a_map, b_map, out_map, bias_map


def _strip_schedule(stationary: str, mb: int, kb: int, nb: int, ns: int):
    """Two-level WS/IS grids (s, ·, k, u): the accumulator strip's k-revisits
    are consecutive (strip pinned in VMEM, level 2) while the stationary
    operand's map ignores the innermost u axis (pinned across the strip's
    inner sweep, level 1).  ``out_map`` is in strip-block coordinates —
    the output block is ``(ns*bm, bn)`` for WS, ``(bm, ns*bn)`` for IS —
    and ignores both k and u, so each strip is copied out exactly once."""
    if stationary == "weight":
        grid = (mb // ns, nb, kb, ns)  # i = s*ns + u
        a_map = lambda s, j, k, u: (s * ns + u, k)
        b_map = lambda s, j, k, u: (k, j)
        out_map = lambda s, j, k, u: (s, j)
        bias_map = lambda s, j, k, u: (0, j)  # block (1, bn)
    elif stationary == "input":
        grid = (nb // ns, mb, kb, ns)  # j = s*ns + u
        a_map = lambda s, i, k, u: (i, k)
        b_map = lambda s, i, k, u: (k, s * ns + u)
        out_map = lambda s, i, k, u: (i, s)
        bias_map = lambda s, i, k, u: (0, s)  # block (1, ns*bn)
    else:  # pragma: no cover
        raise ValueError(stationary)
    return grid, a_map, b_map, out_map, bias_map


def schedule_cost_bytes(
    dataflow: Dataflow,
    M: int,
    K: int,
    N: int,
    block: tuple[int, int, int],
    strip: int = 1,
    in_bytes: int = 4,
    out_bytes: int = 4,
    *,
    a_bytes: int | None = None,
    b_bytes: int | None = None,
) -> int:
    """HBM bytes the kernel's schedule actually moves, counted by walking
    the same grid and index maps the pallas_call builders emit.

    Pallas revisiting semantics: an input block is (re)fetched whenever its
    index-map output changes between consecutive grid steps; an output
    block is written once per run of constant index and read back on every
    revisit after its first (the read-modify-write partial-sum stream).
    ``core.dataflow.hbm_traffic_bytes`` must agree with this walk — the CI
    perf smoke (`benchmarks/train_step.py --verify-traffic`) asserts exact
    equality whenever every GEMM dimension spans >= 2 blocks, and
    walk <= model on degenerate single-block axes (there an idle grid axis
    leaves an index map constant, Pallas coalesces the refetch, and the
    closed form deliberately stays conservative rather than growing
    special cases — it never undercounts, so pruning stays safe).
    Epilogue operands (bias/residual/qscale) are outside both models.

    ``a_bytes`` / ``b_bytes`` give each operand its own element width
    (default ``in_bytes`` for both) — the quantized schedules stream a
    1-byte B against a 2/4-byte A, and the walk must count what the kernel
    actually moves.
    """
    import itertools

    bm, bk, bn = block
    mb, kb, nb = -(-M // bm), -(-K // bk), -(-N // bn)
    if dataflow is Dataflow.OS:
        grid, a_map, b_map, out_map, _ = _os_schedule(mb, kb, nb)
        out_blk = bm * bn
    else:
        stationary = "weight" if dataflow is Dataflow.WS else "input"
        if strip > 1:
            axis_blocks = mb if dataflow is Dataflow.WS else nb
            if axis_blocks % strip:
                raise ValueError(
                    f"strip {strip} does not tile the "
                    f"{'M' if dataflow is Dataflow.WS else 'N'} axis "
                    f"({axis_blocks} blocks) — the kernel would reject this "
                    "schedule, so there is no traffic to count"
                )
            grid, a_map, b_map, out_map, _ = _strip_schedule(
                stationary, mb, kb, nb, strip
            )
            out_blk = strip * bm * bn
        else:
            grid, a_map, b_map, out_map, _ = _stream_schedule(stationary, mb, kb, nb)
            out_blk = bm * bn
    a_blk = bm * bk * (in_bytes if a_bytes is None else a_bytes)
    b_blk = bk * bn * (in_bytes if b_bytes is None else b_bytes)
    total = 0
    prev_a = prev_b = prev_o = None
    seen_out: set[tuple[int, int]] = set()
    for ids in itertools.product(*(range(g) for g in grid)):
        ia, ib, io = a_map(*ids), b_map(*ids), out_map(*ids)
        if ia != prev_a:
            total += a_blk
            prev_a = ia
        if ib != prev_b:
            total += b_blk
            prev_b = ib
        if io != prev_o:  # new output run: one write, plus a read on revisit
            total += out_blk * out_bytes
            if io in seen_out:
                total += out_blk * out_bytes
            seen_out.add(io)
            prev_o = io
    return total


def matmul_os(
    a: jax.Array,
    b: jax.Array,
    *,
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    activation: str | None = None,
    out_dtype: jnp.dtype | None = None,
    block: tuple[int, int, int] = DEFAULT_BLOCK,
    interpret: bool = False,
    save_preact: bool = False,
    trans_a: bool = False,
    trans_b: bool = False,
    strip: int = 1,
    qscale: jax.Array | None = None,
) -> jax.Array | tuple[jax.Array, jax.Array]:
    if strip != 1:
        raise ValueError(
            "OS runs strip=1 only: its accumulator is already VMEM-resident, "
            "and the strip generalisation of OS is the IS strip schedule"
        )
    M, K, N = _logical_dims(a, b, trans_a, trans_b)
    bm, bk, bn = block
    _check(M, K, N, bm, bk, bn)
    grid, a_map, b_map, out_map, bias_map = _os_schedule(M // bm, K // bk, N // bn)
    extra, extra_specs = _epilogue_inputs(
        qscale, bias, residual, bias_map, out_map, bm, bn)
    a_spec, b_spec = _operand_specs(bm, bk, bn, a_map, b_map, trans_a, trans_b)
    kern = functools.partial(
        _os_kernel, activation=activation, has_scale=qscale is not None,
        has_bias=bias is not None, has_res=residual is not None,
        save_preact=save_preact, trans_a=trans_a, trans_b=trans_b,
    )
    out_specs = pl.BlockSpec((bm, bn), out_map)
    out_shape = jax.ShapeDtypeStruct((M, N), out_dtype or jnp.float32)
    if save_preact:
        out_specs = [out_specs, pl.BlockSpec((bm, bn), out_map)]
        out_shape = [out_shape, jax.ShapeDtypeStruct((M, N), jnp.float32)]
    result = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[a_spec, b_spec, *extra_specs],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[_VMEM((bm, bn), jnp.float32)],
        compiler_params=compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(a, b, *extra)
    return (result[0], result[1]) if save_preact else result


def _matmul_stream(
    a: jax.Array,
    b: jax.Array,
    *,
    stationary: str,
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    activation: str | None = None,
    out_dtype: jnp.dtype | None = None,
    block: tuple[int, int, int],
    interpret: bool,
    save_preact: bool = False,
    trans_a: bool = False,
    trans_b: bool = False,
    strip: int = 1,
    qscale: jax.Array | None = None,
) -> jax.Array | tuple[jax.Array, jax.Array]:
    """Shared WS/IS driver.

    ``strip >= 2`` runs the two-level schedule (`_matmul_strip`): partial
    sums accumulate in a VMEM-resident strip, the full epilogue fuses at
    flush.  ``strip = 1`` is the streamed legacy schedule: aliased
    partial-sum accumulation over the outer k axis, bias/activation/cast
    fused in the last-k branch — and the residual added *outside* the
    kernel on the f32 result (same op order, bit-identical; an in-kernel
    fetch under this grid would re-stream the residual every k plane).
    A ``qscale`` forces the fused flush: the staging buffer accumulates
    scaled-lattice partials that must dequantize before leaving the kernel.
    """
    M, K, N = _logical_dims(a, b, trans_a, trans_b)
    bm, bk, bn = block
    _check(M, K, N, bm, bk, bn)
    if strip > 1:
        return _matmul_strip(
            a, b, stationary=stationary, bias=bias, residual=residual,
            activation=activation, out_dtype=out_dtype, block=block,
            interpret=interpret, save_preact=save_preact,
            trans_a=trans_a, trans_b=trans_b, strip=strip, qscale=qscale,
        )
    if not interpret and revisits_output(Dataflow.WS, K, bk):
        raise ValueError(
            f"streamed {stationary}-stationary schedule with K={K} over "
            f"{K // bk} blocks revisits its partial-sum blocks, which Mosaic "
            "never reads back: on the chip use bk=K or an accumulator strip"
        )
    grid, a_map, b_map, c_map, bias_map = _stream_schedule(
        stationary, M // bm, K // bk, N // bn
    )
    a_spec, b_spec = _operand_specs(bm, bk, bn, a_map, b_map, trans_a, trans_b)
    # the kernel casts only when no residual follows: with one, the finished
    # f32 block still needs the (f32) residual added before the final cast
    fused = (
        save_preact or bias is not None or activation is not None
        or qscale is not None
        or (residual is None and out_dtype is not None
            and jnp.dtype(out_dtype) != jnp.float32)
    )
    extra, extra_specs = _epilogue_inputs(
        qscale, bias, None, bias_map, c_map, bm, bn)
    kern = functools.partial(
        _stream_accum_kernel, activation=activation,
        has_scale=qscale is not None, has_bias=bias is not None, fused=fused,
        save_preact=save_preact, trans_a=trans_a, trans_b=trans_b,
    )
    out_specs = pl.BlockSpec((bm, bn), c_map)
    out_shape = jax.ShapeDtypeStruct((M, N), jnp.float32)
    if fused:
        # f32 partial staging buffer + finished output in the target dtype
        kern_dtype = jnp.float32 if residual is not None else (
            out_dtype or jnp.float32)
        out_specs = [out_specs, pl.BlockSpec((bm, bn), c_map)]
        out_shape = [out_shape, jax.ShapeDtypeStruct((M, N), kern_dtype)]
    result = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[a_spec, b_spec, *extra_specs],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=compiler_params("arbitrary", "arbitrary", "arbitrary"),
        interpret=interpret,
    )(a, b, *extra)
    out = result[1] if fused else result
    z = result[0] if save_preact else None
    if residual is not None:
        out = (out + residual.astype(jnp.float32)).astype(
            out_dtype or jnp.float32)
    return (out, z) if save_preact else out


def _matmul_strip(
    a: jax.Array,
    b: jax.Array,
    *,
    stationary: str,
    bias: jax.Array | None,
    residual: jax.Array | None,
    activation: str | None,
    out_dtype: jnp.dtype | None,
    block: tuple[int, int, int],
    interpret: bool,
    save_preact: bool,
    trans_a: bool,
    trans_b: bool,
    strip: int,
    qscale: jax.Array | None = None,
) -> jax.Array | tuple[jax.Array, jax.Array]:
    """Two-level WS/IS driver: VMEM-resident accumulator strip over the
    streamed output axis, one HBM write per output block."""
    M, K, N = _logical_dims(a, b, trans_a, trans_b)
    bm, bk, bn = block
    _check(M, K, N, bm, bk, bn)
    row_strip = stationary == "weight"
    axis_blocks = M // bm if row_strip else N // bn
    if axis_blocks % strip:
        raise ValueError(
            f"strip {strip} must tile the {'M' if row_strip else 'N'} axis "
            f"({axis_blocks} blocks of {bm if row_strip else bn}); "
            "ops.flex_matmul / ops.flex_linear clamp to a feasible strip"
        )
    grid, a_map, b_map, out_map, bias_map = _strip_schedule(
        stationary, M // bm, K // bk, N // bn, strip
    )
    a_spec, b_spec = _operand_specs(bm, bk, bn, a_map, b_map, trans_a, trans_b)
    sblock = (strip * bm, bn) if row_strip else (bm, strip * bn)
    bias_block = (1, bn) if row_strip else (1, strip * bn)
    fused = (
        save_preact
        or bias is not None or residual is not None or activation is not None
        or qscale is not None
        or (out_dtype is not None and jnp.dtype(out_dtype) != jnp.float32)
    )
    extra, extra_specs = [], []
    if qscale is not None:  # rides the bias layout: (1, bn) / (1, ns*bn)
        extra.append(qscale)
        extra_specs.append(pl.BlockSpec(bias_block, bias_map))
    if bias is not None:
        extra.append(bias)
        extra_specs.append(pl.BlockSpec(bias_block, bias_map))
    if residual is not None:  # honest per-strip fetch: map ignores k and u
        extra.append(residual)
        extra_specs.append(pl.BlockSpec(sblock, out_map))
    kern = functools.partial(
        _strip_kernel, activation=activation, has_scale=qscale is not None,
        has_bias=bias is not None, has_res=residual is not None, fused=fused,
        save_preact=save_preact, trans_a=trans_a, trans_b=trans_b,
        ns=strip, row_strip=row_strip,
    )
    out_specs = [pl.BlockSpec(sblock, out_map)]
    out_shape = [jax.ShapeDtypeStruct(
        (M, N), (out_dtype or jnp.float32) if fused else jnp.float32)]
    if save_preact:
        out_specs.append(pl.BlockSpec(sblock, out_map))
        out_shape.append(jax.ShapeDtypeStruct((M, N), jnp.float32))
    scratch = []
    if fused and not save_preact:
        scratch.append(_VMEM(sblock, jnp.float32))
    result = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[a_spec, b_spec, *extra_specs],
        out_specs=out_specs if len(out_specs) > 1 else out_specs[0],
        out_shape=out_shape if len(out_shape) > 1 else out_shape[0],
        scratch_shapes=scratch,
        # (s, j/i) own disjoint output strips — single-writer, so
        # megacore partitioning can engage; k and u stay sequential
        compiler_params=compiler_params("parallel", "parallel", "arbitrary",
                                        "arbitrary"),
        interpret=interpret,
    )(a, b, *extra)
    if save_preact:
        return result[0], result[1]
    return result


def matmul_ws(a, b, *, block=DEFAULT_BLOCK, interpret=False, strip=1,
              **epilogue):
    return _matmul_stream(a, b, stationary="weight", block=block,
                          interpret=interpret, strip=strip, **epilogue)


def matmul_is(a, b, *, block=DEFAULT_BLOCK, interpret=False, strip=1,
              **epilogue):
    return _matmul_stream(a, b, stationary="input", block=block,
                          interpret=interpret, strip=strip, **epilogue)


KERNELS = {
    Dataflow.OS: matmul_os,
    Dataflow.WS: matmul_ws,
    Dataflow.IS: matmul_is,
}


def matmul(
    a: jax.Array,
    b: jax.Array,
    dataflow: Dataflow = Dataflow.OS,
    *,
    block: tuple[int, int, int] = DEFAULT_BLOCK,
    interpret: bool = False,
    trans_a: bool = False,
    trans_b: bool = False,
    strip: int = 1,
) -> jax.Array:
    """Flex matmul: same math, dataflow-selected block schedule.

    ``trans_a`` / ``trans_b`` read the operands in transposed physical
    layout via the index maps — ``op(a) @ op(b)`` with zero HBM copies.
    ``strip >= 2`` selects the two-level WS/IS schedule (VMEM-resident
    accumulator strip; OS rejects it — see module docstring).
    """
    return KERNELS[dataflow](a, b, block=block, interpret=interpret,
                             trans_a=trans_a, trans_b=trans_b, strip=strip)


def fused_matmul(
    a: jax.Array,
    b: jax.Array,
    dataflow: Dataflow = Dataflow.OS,
    *,
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    activation: str | None = None,
    out_dtype: jnp.dtype | None = None,
    block: tuple[int, int, int] = DEFAULT_BLOCK,
    interpret: bool = False,
    save_preact: bool = False,
    trans_a: bool = False,
    trans_b: bool = False,
    strip: int = 1,
    qscale: jax.Array | None = None,
) -> jax.Array | tuple[jax.Array, jax.Array]:
    """Matmul with the epilogue fused into the kernel's final flush.

    ``bias`` must be (1, N); ``residual`` (M, N); all dims block multiples
    (ops.flex_linear pads).  ``activation`` in {relu, gelu, silu, None}.
    With ``save_preact`` returns ``(out, z)`` where ``z`` is the f32
    pre-activation ``a @ b + bias`` — what the custom VJP saves.
    ``trans_a`` / ``trans_b`` read transposed-layout operands in place.
    ``strip >= 2`` runs the two-level WS/IS schedule: the whole epilogue
    (residual included) fuses at the strip flush; with ``strip = 1`` the
    streamed WS/IS kernels fuse bias/activation/cast and the residual is
    added outside the kernel in the same f32 op order (bit-identical).
    ``qscale`` (1, N) f32 marks B as a quantized (int8/fp8) operand with
    per-output-channel scales: the flush dequantizes the f32 accumulator
    before the rest of the epilogue (dequant -> bias -> act -> residual ->
    cast), so quantized and unquantized calls share the epilogue contract.
    """
    if activation is not None and activation not in ACTIVATIONS:
        raise ValueError(f"unknown activation {activation!r}")
    return KERNELS[dataflow](
        a, b, bias=bias, residual=residual, activation=activation,
        out_dtype=out_dtype, block=block, interpret=interpret,
        save_preact=save_preact, trans_a=trans_a, trans_b=trans_b,
        strip=strip, qscale=qscale,
    )
