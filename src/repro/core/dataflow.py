"""Dataflow definitions and analytical cost models.

The Flex-TPU paper's object of study is the *dataflow* of a systolic array:
which operand is pinned ("stationary") in the PEs while the others stream.
This module defines the three dataflows and two cost models over them:

1. ``systolic_cycles`` — a ScaleSim-V2-style analytical clock-cycle model for an
   R x C systolic array executing a GEMM under IS/OS/WS.  This is the model the
   paper's own evaluation (Table I, Figs. 1/6/7) is built on; we re-derive the
   fold/fill/drain arithmetic from the systolic pipeline first principles and
   validate the resulting *per-layer optima and flex speedups* against the
   paper's reported ranges (see tests/test_paper_claims.py).

2. ``hbm_traffic_bytes`` — the TPU-native analogue used by the Pallas kernels:
   for a blocked matmul on a real TPU the "dataflow" is the grid loop order,
   and what differs between IS/OS/WS is how many times each operand's blocks
   are fetched from HBM into VMEM.  The CMU uses this model to pick the
   per-layer dataflow for the kernel path.

Both models are pure functions of layer shape — deliberately so: the paper's
core claim is that the optimum is a function of layer shape, decidable offline.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass


class Dataflow(enum.Enum):
    """The three classic systolic dataflows (paper Section I)."""

    IS = "input_stationary"
    OS = "output_stationary"
    WS = "weight_stationary"

    @property
    def short(self) -> str:
        return self.name


ALL_DATAFLOWS = (Dataflow.IS, Dataflow.OS, Dataflow.WS)


@dataclass(frozen=True)
class GemmShape:
    """A GEMM ``C[M,N] = A[M,K] @ B[K,N]``.

    For a conv layer lowered via im2col (ScaleSim's convention):
      M = output pixels = H_out * W_out  (per image)
      K = R * S * C_in   (filter volume)
      N = C_out          (number of filters)
    For an LM projection: M = tokens, K = d_in, N = d_out.
    """

    M: int
    K: int
    N: int
    name: str = ""

    @property
    def flops(self) -> int:
        return 2 * self.M * self.K * self.N

    @property
    def macs(self) -> int:
        return self.M * self.K * self.N


@dataclass(frozen=True)
class ConvLayer:
    """A convolution layer in the paper's CNN workloads (ScaleSim topology row)."""

    name: str
    ifmap_h: int
    ifmap_w: int
    filt_h: int
    filt_w: int
    channels: int
    num_filters: int
    stride: int

    def out_hw(self) -> tuple[int, int]:
        oh = (self.ifmap_h - self.filt_h) // self.stride + 1
        ow = (self.ifmap_w - self.filt_w) // self.stride + 1
        return max(oh, 1), max(ow, 1)

    def gemm(self) -> GemmShape:
        oh, ow = self.out_hw()
        return GemmShape(
            M=oh * ow,
            K=self.filt_h * self.filt_w * self.channels,
            N=self.num_filters,
            name=self.name,
        )


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def systolic_cycles(shape: GemmShape, dataflow: Dataflow, rows: int, cols: int) -> int:
    """Analytical cycles for one GEMM on an ``rows x cols`` systolic array.

    Fold/fill/drain model (ScaleSim-V2 "analytical" formulation):

    Each dataflow pins one operand tile of at most ``rows x cols`` elements in
    the array ("the fold") and streams a third dimension through it.  A fold
    costs: (preload of the stationary tile, where applicable) + (stream length)
    + (array skew fill/drain ``rows + cols - 2``) + (output drain, where the
    outputs are resident and must be shifted out).

      OS: stationary C tile (rows x cols over M x N); stream K.
          folds = ceil(M/rows) * ceil(N/cols)
          cycles/fold = K + (rows + cols - 2)   [skewed operand fill]
                        + rows                  [shift resident outputs out]
      WS: stationary B tile (rows x cols over K x N); stream M.
          folds = ceil(K/rows) * ceil(N/cols)
          cycles/fold = rows                    [preload weights, row/cycle]
                        + M + (rows + cols - 2) [stream + skew/drain]
      IS: stationary A tile (rows x cols over M x K); stream N.
          folds = ceil(M/rows) * ceil(K/cols)
          cycles/fold = rows                    [preload inputs]
                        + N + (rows + cols - 2)

    Folds are executed back-to-back without overlap (ScaleSim's conservative
    assumption).  The qualitative structure — WS wins when M >> K·N/S², OS wins
    for K-heavy deep layers, IS wins for N-light layers — is exactly the
    paper's Fig. 1 behaviour.
    """
    M, K, N = shape.M, shape.K, shape.N
    skew = rows + cols - 2
    if dataflow is Dataflow.OS:
        folds = _ceil_div(M, rows) * _ceil_div(N, cols)
        per_fold = K + skew + rows
    elif dataflow is Dataflow.WS:
        folds = _ceil_div(K, rows) * _ceil_div(N, cols)
        per_fold = rows + M + skew
    elif dataflow is Dataflow.IS:
        folds = _ceil_div(M, rows) * _ceil_div(K, cols)
        per_fold = rows + N + skew
    else:  # pragma: no cover - exhaustive enum
        raise ValueError(dataflow)
    return folds * per_fold


def best_dataflow(shape: GemmShape, rows: int, cols: int) -> tuple[Dataflow, int]:
    """Exhaustive 3-way search the paper performs offline per layer."""
    best = min(ALL_DATAFLOWS, key=lambda d: systolic_cycles(shape, d, rows, cols))
    return best, systolic_cycles(shape, best, rows, cols)


# ---------------------------------------------------------------------------
# TPU-native (kernel-level) cost model: HBM <-> VMEM block traffic.
# ---------------------------------------------------------------------------

# The single VMEM budget: every flex ``pallas_call`` passes it to Mosaic as
# ``vmem_limit_bytes``, and every planner and feasibility check (analytical
# pruning, measured autotune, strip feasibility, attention and scan
# schedules) admits only schedules whose modelled working set fits under
# it.  A v5e core has 128 MiB of VMEM; 96 MiB leaves the rest to Mosaic's
# internal scratch.  The working-set models below count what the compiler
# allocates, as an upper bound: every input and output block twice (the
# Pallas pipeline double-buffers each), scratch once, the kernel's f32
# temporaries, and each buffer padded to Mosaic's (sublane, 128-lane) tile
# (``vmem_tile_bytes``), so a schedule the model admits compiles.
VMEM_BUDGET_BYTES = 96 * 1024 * 1024


def vmem_tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """Bytes one ``(rows, cols)`` VMEM buffer occupies as Mosaic lays it
    out: rows padded to the dtype's sublane tile (8 rows of 32-bit, 16 of
    16-bit, 32 of 8-bit values), columns to the 128-lane vreg width."""
    sublanes = 32 // min(itemsize, 4)
    return (_ceil_div(rows, sublanes) * sublanes
            * _ceil_div(cols, 128) * 128 * itemsize)


def revisits_output(dataflow: Dataflow, K: int, bk: int,
                    strip: int = 1) -> bool:
    """Whether a GEMM schedule revisits an output block non-consecutively:
    the streamed WS/IS schedule (``strip=1``) with K over more than one
    block comes back to every partial-sum block once per k plane.

    The Pallas interpreter reads such a block back from HBM.  Mosaic does
    not: it writes an output block back when the grid moves off it and
    never reads it in, so on a TPU the partial sums of earlier k planes are
    lost.  Planners offer these schedules only when the kernels run in the
    interpreter, and the kernel builder refuses them on the chip."""
    return (dataflow is not Dataflow.OS and strip == 1
            and _ceil_div(K, bk) > 1)


@dataclass(frozen=True)
class KernelCost:
    """Estimated cost of one blocked matmul under a given dataflow."""

    hbm_bytes: int
    mxu_flops: int
    vmem_bytes: int  # resident working set, must be <= VMEM capacity

    def time_s(self, peak_flops: float = 197e12, hbm_bw: float = 819e9) -> float:
        """Roofline time: max of compute and memory terms."""
        return max(self.mxu_flops / peak_flops, self.hbm_bytes / hbm_bw)


def hbm_traffic_bytes(
    shape: GemmShape,
    dataflow: Dataflow,
    bm: int,
    bk: int,
    bn: int,
    in_bytes: int = 2,
    out_bytes: int = 4,
    strip: int = 1,
    *,
    a_bytes: int | None = None,
    b_bytes: int | None = None,
    scale_bytes: int = 0,
) -> KernelCost:
    """HBM traffic for a blocked matmul with block sizes (bm, bk, bn).

    The Pallas grid order decides block residency (DESIGN.md §2.1):

      OS  grid (i, j, k): C block stays in VMEM across the k loop.
          A fetched Mb*Nb*Kb times? No: A[i,k] changes with (i,k) and is
          re-fetched for each j; B[k,j] re-fetched for each i.
          bytes = Nb * (M*K) * in  +  Mb * (K*N) * in  +  (M*N) * out
      WS  grid (j, k, i): B block pinned across the i loop.
          bytes = (K*N) * in  +  Nb * (M*K) * in  +  Kb * (M*N) * (rw partials)
      IS  grid (i, k, j): A block pinned across the j loop.
          bytes = (M*K) * in  +  Mb * (K*N) * in  +  Kb * (M*N) * (rw partials)

    where Mb=ceil(M/bm) etc.  WS/IS pay partial-sum read+write traffic when
    K doesn't fit one block (Kb > 1); OS never writes partials — this is the
    VMEM-level image of the paper's "outputs accumulate in place" argument.

    **Two-level stationarity (``strip`` >= 2).**  WS/IS can instead pin a
    *strip* of ``strip`` f32 output blocks in VMEM scratch and reorder the
    grid so each strip's k-revisits are consecutive: partial sums never
    touch HBM (one clean write per output block, like OS) and the stationary
    operand stays pinned across the strip's inner sweep exactly as before.
    The price is a re-fetch of the *stationary* operand once per strip —
    the schedule trades ``(2*Kb - 1)`` output round-trips for
    ``ceil(streamed_blocks / strip)`` fetches of the pinned operand:

      WS strip: bytes = ceil(Mb/strip) * (K*N) * in + Nb * (M*K) * in + c
      IS strip: bytes = ceil(Nb/strip) * (M*K) * in + Mb * (K*N) * in + c

    and the VMEM working set grows by the strip's resident output buffers
    (``gemm_vmem_bytes``).  ``strip=1`` is exactly the streamed schedule
    above; OS ignores ``strip`` (its accumulator is already VMEM-resident, and the strip
    generalisation of OS *is* the IS strip schedule).

    **Per-operand dtypes.**  ``in_bytes`` is the legacy both-operands
    width; quantized candidates instead pass ``a_bytes``/``b_bytes``
    explicitly (weight-only quant: ``a_bytes=2, b_bytes=1``) plus
    ``scale_bytes`` for the per-output-channel f32 scale row that streams
    with the B operand — folded into the B term so every refetch factor
    multiplies it too, and into the VMEM working set as one ``bn``-wide
    row per resident B block.

    ``vmem_bytes`` is ``gemm_vmem_bytes`` of the schedule.
    """
    M, K, N = shape.M, shape.K, shape.N
    if a_bytes is None:
        a_bytes = in_bytes
    if b_bytes is None:
        b_bytes = in_bytes
    Mb, Kb, Nb = _ceil_div(M, bm), _ceil_div(K, bk), _ceil_div(N, bn)
    a = M * K * a_bytes
    b = K * N * b_bytes + N * scale_bytes
    c = M * N * out_bytes
    if dataflow is Dataflow.OS:
        hbm = Nb * a + Mb * b + c
    elif dataflow is Dataflow.WS:
        if strip > 1:
            hbm = _ceil_div(Mb, strip) * b + Nb * a + c
        else:
            partial_rw = (2 * Kb - 1) * c if Kb > 1 else c
            hbm = b + Nb * a + partial_rw
    elif dataflow is Dataflow.IS:
        if strip > 1:
            hbm = _ceil_div(Nb, strip) * a + Mb * b + c
        else:
            partial_rw = (2 * Kb - 1) * c if Kb > 1 else c
            hbm = a + Mb * b + partial_rw
    else:  # pragma: no cover
        raise ValueError(dataflow)
    vmem = gemm_vmem_bytes(dataflow, bm, bk, bn, strip, a_bytes=a_bytes,
                           b_bytes=b_bytes, out_bytes=out_bytes,
                           scale_bytes=scale_bytes)
    return KernelCost(hbm_bytes=hbm, mxu_flops=shape.flops, vmem_bytes=vmem)


def gemm_vmem_bytes(dataflow: Dataflow, bm: int, bk: int, bn: int,
                    strip: int = 1, *, a_bytes: int = 2, b_bytes: int = 2,
                    out_bytes: int = 4, scale_bytes: int = 0) -> int:
    """Upper bound on the VMEM one flex-GEMM ``pallas_call`` allocates.

    The output block is ``(bm, bn)``, or the strip's ``(strip*bm, bn)``
    (WS) / ``(bm, strip*bn)`` (IS).  Double-buffered inputs: the A and B
    blocks (each in whichever of its two layouts pads larger, since
    backward GEMMs read transposed operands), and the epilogue operands a
    fused call may carry: the quant scale row, the bias row and the
    residual block.  Double-buffered outputs: the finished block plus an
    f32 block of the same extent (the streamed schedules' partial-sum
    staging buffer, or the saved pre-activation of a training forward).
    Once: OS's f32 accumulator scratch (the strip's scratch is covered by
    the f32 output term, which it replaces when the pre-activation is
    saved).  Temporaries: three f32 ``(bm, bn)`` values, the block product
    and the epilogue's pre-activation and result, and for a quantized B
    (``b_bytes != a_bytes``) the f32 copies of both operand blocks the
    mixed-dtype product is computed on, twice: a v5e has no fp8 unit, and
    Mosaic widens an fp8 block through a 32-bit intermediate of the same
    extent (int8 needs one copy; the bound covers both 1-byte dtypes).
    """
    if strip > 1 and dataflow is Dataflow.WS:
        rows, cols = strip * bm, bn
    elif strip > 1 and dataflow is Dataflow.IS:
        rows, cols = bm, strip * bn
    else:
        rows, cols = bm, bn
    t = vmem_tile_bytes
    ins = (max(t(bm, bk, a_bytes), t(bk, bm, a_bytes))
           + max(t(bk, bn, b_bytes), t(bn, bk, b_bytes))
           + (t(1, cols, scale_bytes) if scale_bytes else 0)
           + t(1, cols, out_bytes) + t(rows, cols, out_bytes))
    outs = t(rows, cols, out_bytes) + t(rows, cols, 4)
    scratch = t(bm, bn, 4) if dataflow is Dataflow.OS else 0
    temps = 3 * t(bm, bn, 4)
    if b_bytes != a_bytes:
        temps += 2 * (t(bm, bk, 4) + t(bk, bn, 4))
    return 2 * ins + 2 * outs + scratch + temps


def strip_blocks(shape: GemmShape, dataflow: Dataflow, bm: int, bn: int) -> int:
    """Block count of the axis a WS/IS accumulator strip tiles (the streamed
    output axis): M-blocks for WS, N-blocks for IS.  1 for OS — its strip
    generalisation is the IS strip schedule, so OS only ever runs strip=1."""
    if dataflow is Dataflow.WS:
        return _ceil_div(shape.M, bm)
    if dataflow is Dataflow.IS:
        return _ceil_div(shape.N, bn)
    return 1


def strip_candidates(n_blocks: int) -> list[int]:
    """Strip depths worth trying over an axis of ``n_blocks`` output blocks:
    every divisor (ragged strips would need masked flushes, so the kernels
    require the strip to tile the axis exactly).  1 = the streamed schedule."""
    if n_blocks <= 1:
        return [1]
    divs = set()
    d = 1
    while d * d <= n_blocks:
        if n_blocks % d == 0:
            divs.add(d)
            divs.add(n_blocks // d)
        d += 1
    return sorted(divs)


def best_kernel_dataflow(
    shape: GemmShape,
    bm: int = 512,
    bk: int = 512,
    bn: int = 512,
    vmem_limit: int = VMEM_BUDGET_BYTES,
) -> tuple[Dataflow, KernelCost]:
    """Pick the dataflow minimising roofline time subject to VMEM fit."""
    candidates: list[tuple[float, Dataflow, KernelCost]] = []
    for df in ALL_DATAFLOWS:
        cost = hbm_traffic_bytes(shape, df, bm, bk, bn, in_bytes=2)
        if cost.vmem_bytes <= vmem_limit:
            candidates.append((cost.time_s(), df, cost))
    if not candidates:
        raise ValueError(f"no dataflow fits VMEM for {shape}")
    _, df, cost = min(candidates, key=lambda t: t[0])
    return df, cost


DEFAULT_BLOCK_CANDIDATES = (128, 256, 512, 1024, 2048, 4096, 8192)

# Sublane-aligned skinny blocks for the M dimension of decode-step GEMMs
# (M = batch, often <= 32): without them the tuner's smallest bm is 128 and
# a 16-row projection models >87% wasted MXU occupancy.  f32 tiles need 8
# sublanes (bf16 wants 16 — the tuner may still pick 8; Mosaic relayouts).
SKINNY_BLOCK_CANDIDATES = (8, 16, 32, 64)


def kernel_block_candidates(
    d: int,
    candidates: tuple[int, ...] = DEFAULT_BLOCK_CANDIDATES,
    sublane: bool = False,
) -> list[int]:
    """MXU-aligned block sizes worth trying for one GEMM dimension of ``d``.

    With ``sublane`` (the M dimension), a dim smaller than one MXU tile also
    offers the sublane-aligned skinny sizes covering it, so skinny GEMMs
    (decode-step projections) are not forced to pad to 128+ rows.
    """
    rounded = max(_ceil_div(d, 128) * 128, 128)
    cs = [c for c in candidates if c <= rounded]
    if sublane and d < 128:
        skinny = [s for s in SKINNY_BLOCK_CANDIDATES if s >= d]
        cs = [s for s in SKINNY_BLOCK_CANDIDATES if s < d] + skinny[:1] + cs
    if rounded <= 16384 and rounded not in cs:
        cs.append(rounded)  # exact-fit block (e.g. bk = K kills partials)
    return cs or [128]


def tune_kernel_dataflow(
    shape: GemmShape,
    vmem_limit: int = VMEM_BUDGET_BYTES,
    candidates: tuple[int, ...] = DEFAULT_BLOCK_CANDIDATES,
) -> tuple[Dataflow, tuple[int, int, int], KernelCost]:
    """Co-tune (dataflow, block shape) under a VMEM budget — streamed
    (strip=1) schedules only; the production tuner that also searches the
    accumulator-strip axis is ``cmu._ranked_candidates``/``autotune_plan``.

    This is the full CMU: the paper tunes which operand is pinned; on TPU the
    block shape decides *how much* of it is pinned, so the two must be chosen
    together.  E.g. with bk >= K the WS/IS partial-sum traffic vanishes and
    WS wins tall training GEMMs while IS wins decode (inputs pinned, weights
    streamed once) — matching the paper's per-layer narrative.
    """

    def blocks_for(d: int) -> list[int]:
        return kernel_block_candidates(d, candidates)

    best: tuple[float, Dataflow, tuple[int, int, int], KernelCost] | None = None
    for df in ALL_DATAFLOWS:
        for bm in blocks_for(shape.M):
            for bk in blocks_for(shape.K):
                for bn in blocks_for(shape.N):
                    cost = hbm_traffic_bytes(shape, df, bm, bk, bn,
                                             in_bytes=2)
                    if cost.vmem_bytes > vmem_limit:
                        continue
                    t = cost.time_s()
                    if best is None or t < best[0] - 1e-18 or (
                        abs(t - best[0]) < 1e-18 and cost.hbm_bytes < best[3].hbm_bytes
                    ):
                        best = (t, df, (bm, bk, bn), cost)
    assert best is not None
    return best[1], best[2], best[3]


def arithmetic_intensity(shape: GemmShape, in_bytes: int = 2, out_bytes: int = 2) -> float:
    """FLOPs per HBM byte at perfect reuse (the roofline upper bound)."""
    bytes_min = (shape.M * shape.K + shape.K * shape.N) * in_bytes + shape.M * shape.N * out_bytes
    return shape.flops / bytes_min


def mxu_utilization(shape: GemmShape, mxu: int = 128) -> float:
    """Fraction of MXU lanes busy given dimension padding to the MXU size."""

    def pad(d: int) -> int:
        return _ceil_div(d, mxu) * mxu

    return (shape.M * shape.K * shape.N) / (pad(shape.M) * pad(shape.K) * pad(shape.N))


# ---------------------------------------------------------------------------
# Attention: the flash-kernel schedule family's analytical cost model.
# ---------------------------------------------------------------------------

#: (bq, bk) candidates for the prefill flash-attention sweep.  Smaller than
#: the GEMM grid: score tiles are (bq, bk) f32 in VMEM and the row axis of a
#: smoke-sized prefill rarely exceeds a few hundred.
ATTN_BLOCK_CANDIDATES = (64, 128, 256, 512)


@dataclass(frozen=True)
class AttnShape:
    """Planning fingerprint of one self-attention op (per layer shape, like
    ``GemmShape`` for projections).  ``seq``/``kv`` are query / key lengths,
    heads are the model's query and KV head counts.  The GQA group axis is
    folded into rows exactly as ``kernels.flash_attention.mha_flash`` does,
    so the model prices what the kernel actually runs."""

    seq: int
    kv: int
    heads: int
    kv_heads: int
    head_dim: int
    name: str = "attn.sdpa"

    @property
    def group(self) -> int:
        return max(self.heads // self.kv_heads, 1)

    @property
    def rows(self) -> int:
        """Q rows per (batch, kv-head) kernel instance after GQA folding."""
        return self.group * self.seq

    @property
    def flops(self) -> int:
        # QK^T and PV each: 2 * rows * kv * hd MACs-as-flops, per kv head.
        return 4 * self.kv_heads * self.rows * self.kv * self.head_dim

    @property
    def macs(self) -> int:
        return self.flops // 2


def attn_traffic_bytes(shape: AttnShape, sweep: str, bq: int, bk: int,
                       in_bytes: int = 2, out_bytes: int = 2) -> KernelCost:
    """HBM traffic + VMEM residency of one prefill flash-attention schedule.

    Mirrors ``hbm_traffic_bytes`` for the attention grid.  Per kv head:

      q-stationary:  q + o move once; K/V re-stream once per q tile:
          hbm  = q_bytes + nq * kv_bytes + o_bytes
      kv-stationary: K/V move once; q re-streams once per kv tile, and the
      whole-rows accumulator slab (f32 acc + m/l stats) is VMEM-resident
      so the output flushes exactly once:
          hbm  = kv_bytes + nkv * q_bytes + o_bytes

    VMEM, as the compiler allocates it: the q, k, v blocks and the output
    block (``(bq, hd)``, or the whole ``(rows, hd)`` rows for
    kv-stationary) double-buffered; the f32 accumulator and the m/l stats
    (one lane-padded column each) over the same rows; the f32 copies of
    the q, k, v blocks the kernel computes in and the ``(bq, bk)`` score
    and probability tiles.

    The kv-stationary HBM win scales with ``nq = rows / bq`` — i.e. with
    the GQA group and context length — which is exactly the paper's
    shape-decides-the-dataflow argument transplanted to attention.
    """
    if sweep not in ("q", "kv"):
        raise ValueError(f"unknown attention sweep {sweep!r}")
    rows, kv, hd = shape.rows, shape.kv, shape.head_dim
    bq, bk = min(bq, rows), min(bk, kv)
    nq, nkv = _ceil_div(rows, bq), _ceil_div(kv, bk)
    q_bytes = rows * hd * in_bytes
    kv_bytes = 2 * kv * hd * in_bytes
    o_bytes = rows * hd * out_bytes
    t = vmem_tile_bytes
    if sweep == "q":
        hbm = shape.kv_heads * (q_bytes + nq * kv_bytes + o_bytes)
        out_rows = bq
    else:
        hbm = shape.kv_heads * (kv_bytes + nkv * q_bytes + o_bytes)
        out_rows = rows
    vmem = (2 * (t(bq, hd, in_bytes) + 2 * t(bk, hd, in_bytes)
                 + t(out_rows, hd, out_bytes))
            + t(out_rows, hd, 4) + 2 * t(out_rows, 1, 4)
            + t(bq, hd, 4) + 2 * t(bk, hd, 4) + 2 * t(bq, bk, 4))
    return KernelCost(hbm_bytes=hbm, mxu_flops=shape.flops, vmem_bytes=vmem)


#: Tunable chunk lengths for the flex chunked-scan family.  Exp-safety bounds
#: the ladder: every in-chunk exponent is within ``|LOG_DECAY_MIN| * chunk =
#: 3 * chunk`` (models.ssm), so all candidates keep exp() arguments well
#: inside f32 range (limit ~88).
SCAN_CHUNK_CANDIDATES = (8, 16, 24)


@dataclass(frozen=True)
class ScanShape:
    """Planning fingerprint of one chunked diagonal-decay scan (per layer
    shape, like ``AttnShape`` for attention).  ``seq`` is the (padded)
    token count per batch row, ``heads`` the recurrence head count,
    ``key_dim``/``val_dim`` the (N, M) state slab sides.  ``post_update``
    records the recurrence convention (True = Mamba2, False = RWKV) — it
    changes the fused epilogue the kernel runs, so measured timings key on
    it."""

    batch: int
    seq: int
    heads: int
    key_dim: int   # N: decay/state rows
    val_dim: int   # M: value/state cols
    post_update: bool = False
    name: str = "ssm.scan"

    @property
    def bh(self) -> int:
        """Folded (batch, head) kernel instances."""
        return self.batch * self.heads

    @property
    def state_bytes(self) -> int:
        """The full f32 state slab the "state" sweep pins in VMEM."""
        return self.bh * self.key_dim * self.val_dim * 4

    @property
    def flops(self) -> int:
        # per token: L-wide score row (N), output row (M), and the rank-1
        # state update + inter-chunk read (2*N*M) — L taken at the default
        # 16-chunk so the fingerprint doesn't depend on the tuned schedule
        L = 16
        per_tok = L * (self.key_dim + self.val_dim) + 2 * self.key_dim * self.val_dim
        return 2 * self.bh * self.seq * per_tok

    @property
    def macs(self) -> int:
        return self.flops // 2


def scan_traffic_bytes(shape: ScanShape, sweep: str, chunk: int,
                       in_bytes: int = 2, out_bytes: int = 2) -> KernelCost:
    """HBM traffic + VMEM residency of one chunked-scan schedule.

    Mirrors ``attn_traffic_bytes`` for the scan grid (C chunks outer x B*H
    inner, one (L, .) tile set per step).  The r/k/v/log_w inputs and the o
    output move exactly once under *both* sweeps (every block is visited
    once); the sweeps differ only in how the running (N, M) f32 state
    travels:

      state-stationary: the whole ``bh*N*M`` f32 slab is a never-moving
      output block — VMEM-resident across the grid, written once:
          hbm  = streams + state_bytes
      output-stationary: the state is a per-(b,h) block revisited
      non-consecutively across the chunk axis, so it round-trips HBM every
      chunk step (read-modify-write), and VMEM holds just one block:
          hbm  = streams + 2 * C * state_bytes

    VMEM, as the compiler allocates it: one step's r/k/v/log_w/u input
    blocks, the o block and the state block (the whole slab, or one
    ``(N, M)`` block) double-buffered, plus the step's f32 temporaries
    (compute copies of the tiles, the ``(L, L)`` score tile, the new state).

    The state-stationary HBM win scales with C = seq/chunk; its VMEM cost
    scales with ``batch*heads*N*M`` — which is exactly the paper's
    shape-decides-the-dataflow argument transplanted to the scan: long
    prefills at small batch want "state", large-batch prefills overflow the
    96 MiB budget and fall back to "out".
    """
    if sweep not in ("state", "out"):
        raise ValueError(f"unknown scan sweep {sweep!r}")
    T, n, m = shape.seq, shape.key_dim, shape.val_dim
    L = min(chunk, T)
    C = _ceil_div(T, L)
    # per-(b,h) sequential streams, each moved exactly once
    rk_bytes = 2 * T * n * in_bytes          # r, k
    lw_bytes = T * n * 4                     # log_w (f32)
    v_bytes = T * m * in_bytes
    o_bytes = T * m * out_bytes
    streams = shape.bh * (rk_bytes + lw_bytes + v_bytes + o_bytes)
    t = vmem_tile_bytes
    ins = (2 * t(L, n, in_bytes) + t(L, m, in_bytes) + t(L, n, 4)
           + t(1, n, 4))
    temps = (3 * t(L, n, 4) + 2 * t(L, m, 4) + t(L, L, 4) + t(n, m, 4))
    if sweep == "state":
        hbm = streams + shape.state_bytes
        state_blk = t(shape.bh * n, m, 4)
    else:
        hbm = streams + 2 * C * shape.state_bytes
        state_blk = t(n, m, 4)
    vmem = 2 * (ins + t(L, m, out_bytes) + state_blk) + temps
    return KernelCost(hbm_bytes=hbm, mxu_flops=shape.flops, vmem_bytes=vmem)


def scan_decode_traffic_bytes(shape: ScanShape, kind: str, bucket: int,
                              in_bytes: int = 2,
                              out_bytes: int = 2) -> KernelCost:
    """HBM traffic of one bucketed decode-scan step.

    ``kind="fused"`` runs the single Pallas step kernel: state in + state
    out, one HBM round trip.  ``kind="einsum"`` is the jnp recurrence,
    which materializes the ``k v^T`` outer product as an HBM intermediate
    between ops — an extra state-sized write + read (3x the state bytes).
    The analytical gap makes "fused" the default pick; a measured run can
    still override it per bucket.
    """
    if kind not in ("fused", "einsum"):
        raise ValueError(f"unknown decode scan kind {kind!r}")
    n, m = shape.key_dim, shape.val_dim
    bh = bucket * shape.heads
    state = bh * n * m * 4
    io = bh * (3 * n * in_bytes + n * 4 + m * in_bytes + m * out_bytes)
    flops = 2 * bh * (2 * n * m + n + m)
    if kind == "fused":
        hbm = io + 2 * state
        vmem = io + 2 * state  # whole-problem blocks, no grid
    else:
        hbm = io + 3 * state + state  # + kv intermediate round trip
        vmem = 2 * state
    return KernelCost(hbm_bytes=hbm, mxu_flops=flops, vmem_bytes=vmem)


def attn_decode_traffic_bytes(shape: AttnShape, kind: str, bucket: int,
                              cache_len: int | None = None,
                              block_size: int = 16,
                              in_bytes: int = 2,
                              out_bytes: int = 2) -> KernelCost:
    """HBM traffic of one bucketed decode-attention step over a paged cache.

    ``kind="paged"`` reads each K/V block from the pool exactly once, in
    place; ``kind="gather"`` is the pure-jnp baseline, which reads the pool,
    writes a densified (bucket, cache_len) copy, then reads it back — 3x the
    cache bytes.  The analytical gap is what makes the paged kernel the
    default pick; a measured run can still override it per bucket.
    """
    if kind not in ("paged", "gather"):
        raise ValueError(f"unknown decode attention kind {kind!r}")
    kv = cache_len if cache_len is not None else shape.kv
    hd, hkv = shape.head_dim, shape.kv_heads
    q_bytes = bucket * shape.heads * hd * in_bytes
    o_bytes = bucket * shape.heads * hd * out_bytes
    cache_bytes = 2 * bucket * kv * hkv * hd * in_bytes
    flops = 4 * bucket * shape.heads * kv * hd
    if kind == "paged":
        hbm = q_bytes + cache_bytes + o_bytes
        # double-buffered (H, hd) q/o blocks and (bs, Hkv, hd) K/V blocks,
        # the (Hkv, group, .) f32 accumulator and m/l scratch, and the f32
        # score/probability tiles
        t = vmem_tile_bytes
        group = shape.group
        vmem = (2 * (t(shape.heads, hd, in_bytes) + t(shape.heads, hd, out_bytes)
                     + 2 * block_size * t(hkv, hd, in_bytes))
                + hkv * (t(group, hd, 4) + 2 * t(group, 1, 4)
                         + 2 * t(group, block_size, 4)))
    else:
        hbm = q_bytes + 3 * cache_bytes + o_bytes
        vmem = (shape.heads * hd + 2 * kv * hkv * hd) * in_bytes
    return KernelCost(hbm_bytes=hbm, mxu_flops=flops, vmem_bytes=vmem)
