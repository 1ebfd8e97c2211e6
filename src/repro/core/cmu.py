"""Configuration Management Unit (CMU) — offline per-layer dataflow selection.

Paper Section II: "To find the optimal dataflow strategy for each layer in the
DNN, we should run each trained model on the Flex-TPU three times, once for
each dataflow, during the development phase. [...] the optimal dataflow is
then programmed into the CMU".

We implement that exact pre-deployment procedure at three levels:

* ``plan_systolic``  — the faithful reproduction: 3 simulator runs per layer,
  keep the per-layer argmin (drives Table I / Fig. 6 / Fig. 7 benchmarks).
* ``plan_kernels``   — the TPU-native port: 3 HBM-traffic evaluations per GEMM
  in an LM architecture, keep the per-layer roofline-argmin.
* ``autotune_plan``  — the production tuner: the analytical model *prunes*
  the (dataflow, block) candidate set, then each survivor is timed with real
  kernel executions (interpret-mode walltime on CPU, on-device walltime on
  TPU) — the paper's "run each model three times" made literal, per candidate.
  This mirrors FlexNN (Raha et al., 2024): per-layer dataflow selection pays
  off most when the selector is driven by measured cost, not a single
  analytical model.

**Training plans.**  ``autotune_plan(..., train=True)`` plans the *three*
GEMMs of each layer as a group — the forward ``C[M,N] = A[M,K] @ B[K,N]``
plus its two cotangent GEMMs ``dX = dY @ W^T`` ((M,N)x(N,K)) and
``dW = X^T @ dY`` ((K,M)x(M,N)).  The backward shapes transpose the
forward's aspect ratio, so they generally want *different* dataflows (e.g.
a WS-favouring tall fwd GEMM yields an OS-favouring dW) — the paper's
per-layer reconfiguration argument applied within a single training step.
The sub-plans land in ``LayerPlan.bwd_dx`` / ``bwd_dw`` and flow through
``models.layers.linear`` into ``ops.flex_linear``'s custom VJP.

The winning ``DataflowPlan`` (now carrying block shapes and optional
backward sub-plans) is persisted as JSON via ``core.plan_cache`` so
serve/train reload plans instead of re-tuning.  All selection remains
one-time, offline, and trace-time static — exactly the paper's deployment
model (no lax.switch on the hot path).
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import NamedTuple

from .dataflow import (
    ALL_DATAFLOWS,
    ATTN_BLOCK_CANDIDATES,
    SCAN_CHUNK_CANDIDATES,
    VMEM_BUDGET_BYTES,
    AttnShape,
    ConvLayer,
    Dataflow,
    GemmShape,
    ScanShape,
    attn_decode_traffic_bytes,
    attn_traffic_bytes,
    best_kernel_dataflow,
    hbm_traffic_bytes,
    kernel_block_candidates,
    revisits_output,
    scan_decode_traffic_bytes,
    scan_traffic_bytes,
    strip_blocks,
    strip_candidates,
    systolic_cycles,
    tune_kernel_dataflow,
)
from .dist_dataflow import MeshSpec, best_mesh_dataflow


class EpilogueSig(NamedTuple):
    """The epilogue signature of one layer's forward GEMM — what
    ``measure_kernel`` times when the autotune is epilogue-aware, so the
    measured op matches the op the model actually issues."""

    activation: str | None = None
    bias: bool = False
    residual: bool = False


def _epilogue_sig(epilogue) -> EpilogueSig | None:
    """Normalise a ``measure_kernel``/``autotune_plan`` epilogue argument:
    False/None -> bare matmul, True -> the legacy bias+gelu probe, an
    ``EpilogueSig`` -> itself."""
    if isinstance(epilogue, EpilogueSig):
        return epilogue
    if epilogue:
        return EpilogueSig(activation="gelu", bias=True)
    return None


# Zero-copy operand layouts of the two backward GEMM roles (trans_a, trans_b):
# dX = dY @ W^T streams W as stored via trans_b; dW = X^T @ dY streams X as
# stored via trans_a.  (False, False) is the copy-based fallback.
TRANS_DX = (False, True)
TRANS_DW = (True, False)
NO_TRANS = (False, False)

# Serving batch-size buckets the CMU keys decode GEMM plans on — the
# sublane-aligned skinny-bm candidates (kernel_block_candidates(M,
# sublane=True)), so a continuous-batching scheduler that quantizes its live
# batch to these sizes dispatches a plan whose bm never pads past the batch.
DECODE_BUCKETS = (8, 16, 32, 64)


def decode_bucket(m: int, buckets: tuple[int, ...] = DECODE_BUCKETS) -> int | None:
    """The smallest bucket that fits an ``m``-row decode GEMM, or None when
    ``m`` exceeds every bucket (prefill-sized batches keep the forward plan)."""
    for b in sorted(buckets):
        if m <= b:
            return b
    return None


#: The layer row an attention schedule rides on.  Attention is not a GEMM the
#: plan fingerprints (``plan_matches`` keys on (name, M, K, N)), so its
#: schedule attaches to the query projection's row — one attention op per
#: transformer layer shape, planned next to the projections that feed it.
ATTN_ANCHOR = "attn.wq"

#: Prefill sweep orders / decode kinds, mirroring
#: ``kernels.flash_attention.ATTN_SWEEPS`` / ``ATTN_DECODE_KINDS`` (kept as
#: literals here so the planning layer never imports kernel modules at
#: module scope).
ATTN_SWEEPS = ("q", "kv")
ATTN_DECODE_KINDS = ("paged", "gather")

#: The layer row a chunked-scan schedule rides on.  Like attention, the SSM
#: scan is not a GEMM the plan fingerprints, so its schedule attaches to the
#: one row every family emits — the lm_head projection (SSM/hybrid configs
#: have no ``attn.wq`` usage of their own; hybrid's shared block does, but
#: the scan is a property of the *backbone* layers, not that one block).
SCAN_ANCHOR = "lm_head"

#: Chunk-grid sweep orders / decode kinds, mirroring
#: ``kernels.flex_scan.SCAN_SWEEPS`` / ``SCAN_DECODE_KINDS`` (kept as
#: literals here so the planning layer never imports kernel modules at
#: module scope).
SCAN_SWEEPS = ("state", "out")
SCAN_DECODE_KINDS = ("fused", "einsum")


@dataclass(frozen=True)
class AttnPlan:
    """One flash-attention schedule decision — the attention analogue of
    ``GemmPlan``.  For the prefill row, ``sweep`` is the grid order
    (``"q"`` / ``"kv"``) and ``block`` the ``(bq, bk)`` tile shape.  For
    the per-bucket ``decode`` sub-plans, ``sweep`` is the decode *kind*
    (``"paged"`` = the in-place Pallas block-table kernel, ``"gather"`` =
    the pure-jnp densify baseline) and ``block`` is empty."""

    sweep: str
    block: tuple[int, ...]
    est_cost: float
    source: str = "analytical"  # "analytical" | "measured"
    # decode sub-plans keyed by batch-size bucket, mirroring
    # ``LayerPlan.decode``.  None = planned before serving buckets existed.
    decode: dict[int, "AttnPlan"] | None = None

    def decode_plan(self, m: int) -> "AttnPlan | None":
        """The decode-attention sub-plan for an ``m``-slot dispatch: the
        smallest tuned bucket that fits, else None (caller keeps the
        gather baseline)."""
        if not self.decode:
            return None
        b = decode_bucket(m, tuple(self.decode))
        return self.decode.get(b) if b is not None else None

    def to_row(self) -> dict:
        return {
            "sweep": self.sweep,
            "block": list(self.block),
            "est_cost": self.est_cost,
            "source": self.source,
            "decode": {str(b): p.to_row() for b, p in sorted(self.decode.items())}
            if self.decode else None,
        }

    @classmethod
    def from_row(cls, row: dict | None) -> "AttnPlan | None":
        if row is None:
            return None
        dec = row.get("decode")
        return cls(
            sweep=row["sweep"],
            block=tuple(row.get("block") or ()),
            est_cost=row["est_cost"],
            source=row.get("source", "analytical"),
            decode={int(b): cls.from_row(r) for b, r in dec.items()}
            if dec else None,
        )


@dataclass(frozen=True)
class ScanPlan:
    """One chunked-scan schedule decision — the SSM analogue of
    ``AttnPlan``.  For the prefill row, ``sweep`` is where the running
    (N, M) state lives across the chunk grid (``"state"`` = VMEM-resident
    slab, ``"out"`` = HBM-streamed per-(b,h) block) and ``chunk`` the
    intra-chunk length L.  For the per-bucket ``decode`` sub-plans,
    ``sweep`` is the decode *kind* (``"fused"`` = the single Pallas step
    kernel, ``"einsum"`` = the jnp recurrence) and ``chunk`` is 0."""

    sweep: str
    chunk: int
    est_cost: float
    source: str = "analytical"  # "analytical" | "measured"
    # decode sub-plans keyed by batch-size bucket, mirroring
    # ``AttnPlan.decode``.  None = planned before serving buckets existed.
    decode: dict[int, "ScanPlan"] | None = None

    def decode_plan(self, m: int) -> "ScanPlan | None":
        """The decode-scan sub-plan for an ``m``-slot dispatch: the smallest
        tuned bucket that fits, else None (caller keeps the fused
        default)."""
        if not self.decode:
            return None
        b = decode_bucket(m, tuple(self.decode))
        return self.decode.get(b) if b is not None else None

    def to_row(self) -> dict:
        return {
            "sweep": self.sweep,
            "chunk": self.chunk,
            "est_cost": self.est_cost,
            "source": self.source,
            "decode": {str(b): p.to_row() for b, p in sorted(self.decode.items())}
            if self.decode else None,
        }

    @classmethod
    def from_row(cls, row: dict | None) -> "ScanPlan | None":
        if row is None:
            return None
        dec = row.get("decode")
        return cls(
            sweep=row["sweep"],
            chunk=int(row.get("chunk") or 0),
            est_cost=row["est_cost"],
            source=row.get("source", "analytical"),
            decode={int(b): cls.from_row(r) for b, r in dec.items()}
            if dec else None,
        )


@dataclass(frozen=True)
class GemmPlan:
    """One (dataflow, block, operand-layout, strip) decision for a single
    GEMM — the unit the CMU programs.  Used for the backward sub-plans
    carried by ``LayerPlan``.  ``trans`` is the ``(trans_a, trans_b)`` the
    kernel runs with: the zero-copy transposed-operand variant for backward
    GEMMs, or ``(False, False)`` when the copy-based fallback measured
    faster.  ``strip`` is the WS/IS accumulator-strip depth: 1 streams
    partial sums through HBM (the pre-v4 schedule, and the only OS value);
    >= 2 pins a VMEM-resident strip so partials never leave the chip.

    ``qdtype`` is the operand-precision decision (v9): ``None`` = the plan
    predates quant tuning (v1–v8) or quant was never requested; ``"bf16"``
    = quant was searched and rejected (accuracy gate failed, or the
    unquantized candidate measured faster); ``"int8"`` / ``"fp8"`` = the
    dispatch quantizes the weight per output channel.  ``qerror`` records
    the measured calibration error of the chosen quantized dtype (None for
    unquantized picks)."""

    dataflow: Dataflow
    block: tuple[int, int, int] | None
    est_cost: float
    source: str = "analytical"  # "analytical" | "measured"
    trans: tuple[bool, bool] = NO_TRANS
    strip: int = 1
    qdtype: str | None = None
    qerror: float | None = None

    def to_row(self) -> dict:
        return {
            "dataflow": self.dataflow.name,
            "block": list(self.block) if self.block else None,
            "est_cost": self.est_cost,
            "source": self.source,
            "trans": list(self.trans),
            "strip": self.strip,
            "qdtype": self.qdtype,
            "qerror": self.qerror,
        }

    @classmethod
    def from_row(cls, row: dict | None) -> "GemmPlan | None":
        if row is None:
            return None
        blk = row.get("block")
        trans = row.get("trans")
        return cls(
            dataflow=Dataflow[row["dataflow"]],
            block=tuple(blk) if blk else None,
            est_cost=row["est_cost"],
            source=row.get("source", "analytical"),
            trans=tuple(bool(t) for t in trans) if trans else NO_TRANS,
            strip=int(row.get("strip") or 1),
            qdtype=row.get("qdtype"),
            qerror=row.get("qerror"),
        )


@dataclass(frozen=True)
class MeshPlan:
    """The second CMU planning level: how one layer's GEMM is composed
    across the mesh's tensor axis, and the local per-shard kernel geometry
    under that composition.

    ``dataflow`` is the *mesh-level* stationarity (``dist_dataflow``): WS
    emits all-gather(A) + reduce-scatter(C) around the weight-sharded local
    kernel, IS all-gathers the weight shard, OS runs the rotating
    collective-permute SUMMA schedule.  ``local`` / ``local_dx`` /
    ``local_dw`` are chip-level ``GemmPlan``s tuned for the
    *post-collective* shard shapes (``mesh_local_gemm``) — the shapes the
    pallas_call inside the shard_map actually sees.
    """

    dataflow: Dataflow          # mesh-level stationarity
    axis: str                   # tensor-axis name the collectives run over
    tp: int                     # its extent when planned
    dp: int                     # data-parallel degree when planned
    local: GemmPlan             # local per-shard forward GEMM geometry
    local_dx: GemmPlan | None = None  # local backward sub-geometries
    local_dw: GemmPlan | None = None
    comm_bytes: int = 0         # modeled ICI bytes/chip (mesh cost model)

    def to_row(self) -> dict:
        return {
            "dataflow": self.dataflow.name,
            "axis": self.axis,
            "tp": self.tp,
            "dp": self.dp,
            "comm_bytes": self.comm_bytes,
            "local": self.local.to_row(),
            "local_dx": self.local_dx.to_row() if self.local_dx else None,
            "local_dw": self.local_dw.to_row() if self.local_dw else None,
        }

    @classmethod
    def from_row(cls, row: dict | None) -> "MeshPlan | None":
        if row is None:
            return None
        return cls(
            dataflow=Dataflow[row["dataflow"]],
            axis=row["axis"],
            tp=int(row["tp"]),
            dp=int(row["dp"]),
            local=GemmPlan.from_row(row["local"]),
            local_dx=GemmPlan.from_row(row.get("local_dx")),
            local_dw=GemmPlan.from_row(row.get("local_dw")),
            comm_bytes=int(row.get("comm_bytes") or 0),
        )


@dataclass(frozen=True)
class LayerPlan:
    name: str
    gemm: GemmShape
    dataflow: Dataflow
    est_cost: float  # cycles (systolic), seconds (roofline), or measured s
    block: tuple[int, int, int] | None = None  # (bm, bk, bn) when co-tuned
    source: str = "analytical"  # "analytical" | "measured"
    # training sub-plans: the layer's two cotangent GEMMs (None = fwd-only)
    bwd_dx: GemmPlan | None = None  # dX = dY @ W^T, an (M,N)x(N,K) GEMM
    bwd_dw: GemmPlan | None = None  # dW = X^T @ dY, a (K,M)x(M,N) GEMM
    strip: int = 1  # forward accumulator-strip depth (1 = streamed)
    # mesh sub-plan: the distributed composition (None = single-device only)
    mesh: MeshPlan | None = None
    # decode sub-plans keyed by batch-size bucket (DECODE_BUCKETS): the same
    # (K, N) projection tuned at M = bucket rows, so the serving decode step
    # dispatches a skinny-bm geometry instead of the prefill-sized forward
    # row.  None = plan predates serving (v1–v5) or was tuned without buckets.
    decode: dict[int, GemmPlan] | None = None
    # flash-attention schedule (prefill sweep/blocks + per-bucket decode
    # kinds), carried only by the ``ATTN_ANCHOR`` row.  None = plan predates
    # attention scheduling (v1–v6) or was tuned without an attention shape.
    attention: AttnPlan | None = None
    # chunked-scan schedule (prefill sweep/chunk + per-bucket decode kinds),
    # carried only by the ``SCAN_ANCHOR`` row.  None = plan predates scan
    # scheduling (v1–v7) or was tuned without a scan shape.
    scan: ScanPlan | None = None
    # forward operand-precision decision (v9), mirroring ``GemmPlan.qdtype``:
    # None = plan predates quant tuning (v1–v8) or quant was never requested,
    # "bf16" = quant searched and rejected, "int8"/"fp8" = the forward
    # dispatch quantizes the weight per output channel.
    qdtype: str | None = None
    qerror: float | None = None

    def decode_plan(self, m: int) -> GemmPlan | None:
        """The decode sub-plan for an ``m``-row dispatch: the smallest tuned
        bucket that fits, or None (caller keeps the forward decision) when no
        buckets were tuned or ``m`` exceeds them all."""
        if not self.decode:
            return None
        b = decode_bucket(m, tuple(self.decode))
        return self.decode.get(b) if b is not None else None


@dataclass
class DataflowPlan:
    """The CMU's program: one dataflow (+ block shape) per layer, decided
    pre-deployment.  ``mesh`` records the mesh fingerprint the per-layer
    mesh sub-plans were tuned for (None = single-device plan)."""

    layers: list[LayerPlan] = field(default_factory=list)
    mesh: MeshSpec | None = None

    def get(self, name: str) -> LayerPlan | None:
        for l in self.layers:
            if l.name == name:
                return l
        return None

    def dataflow_for(self, name: str) -> Dataflow:
        lp = self.get(name)
        if lp is None:
            raise KeyError(name)
        return lp.dataflow

    def histogram(self) -> dict[str, int]:
        h = {df.name: 0 for df in ALL_DATAFLOWS}
        for l in self.layers:
            h[l.dataflow.name] += 1
        return h

    def has_bwd(self) -> bool:
        """True when every layer carries both backward sub-plans — the bar
        a plan must clear before it can drive ``--pallas`` training."""
        return bool(self.layers) and all(
            l.bwd_dx is not None and l.bwd_dw is not None for l in self.layers
        )

    def has_decode(self, buckets: tuple[int, ...]) -> bool:
        """True when every layer carries a decode sub-plan for every
        requested bucket — the bar a plan must clear before it can drive a
        bucketed serving run without re-tuning."""
        return bool(self.layers) and all(
            l.decode is not None and all(b in l.decode for b in buckets)
            for l in self.layers
        )

    def has_attention(self, buckets: tuple[int, ...] = ()) -> bool:
        """True when the anchor row carries an attention schedule, including
        a decode sub-plan for every requested bucket — the bar a plan must
        clear before it can drive ``attn_pallas`` without re-tuning."""
        lp = self.get(ATTN_ANCHOR)
        if lp is None or lp.attention is None:
            return False
        if not buckets:
            return True
        dec = lp.attention.decode
        return dec is not None and all(b in dec for b in buckets)

    def attention_plan(self) -> AttnPlan | None:
        """The model's attention schedule (rides the ``ATTN_ANCHOR`` row)."""
        lp = self.get(ATTN_ANCHOR)
        return lp.attention if lp is not None else None

    def has_scan(self, buckets: tuple[int, ...] = ()) -> bool:
        """True when the anchor row carries a chunked-scan schedule,
        including a decode sub-plan for every requested bucket — the bar a
        plan must clear before it can drive ``ssm_pallas`` without
        re-tuning."""
        lp = self.get(SCAN_ANCHOR)
        if lp is None or lp.scan is None:
            return False
        if not buckets:
            return True
        dec = lp.scan.decode
        return dec is not None and all(b in dec for b in buckets)

    def scan_plan(self) -> ScanPlan | None:
        """The model's chunked-scan schedule (rides the ``SCAN_ANCHOR``
        row)."""
        lp = self.get(SCAN_ANCHOR)
        return lp.scan if lp is not None else None

    def has_quant(self, buckets: tuple[int, ...] = ()) -> bool:
        """True when every layer (and every requested decode bucket) carries
        a quant verdict — the bar a plan must clear before it can drive
        ``--quant`` without re-tuning.  A "bf16" verdict counts: quant was
        searched and rejected by the accuracy gate or the ranking, which is
        a decision, not an omission."""
        if not self.layers:
            return False
        for l in self.layers:
            if l.qdtype is None:
                return False
            for b in buckets:
                gp = (l.decode or {}).get(b)
                if gp is None or gp.qdtype is None:
                    return False
        return True

    def to_json(self) -> str:
        return json.dumps(
            [
                {
                    "name": l.name,
                    "M": l.gemm.M,
                    "K": l.gemm.K,
                    "N": l.gemm.N,
                    "dataflow": l.dataflow.name,
                    "est_cost": l.est_cost,
                    "block": list(l.block) if l.block else None,
                    "source": l.source,
                    "strip": l.strip,
                    "bwd_dx": l.bwd_dx.to_row() if l.bwd_dx else None,
                    "bwd_dw": l.bwd_dw.to_row() if l.bwd_dw else None,
                    "mesh": l.mesh.to_row() if l.mesh else None,
                    "decode": {str(b): gp.to_row() for b, gp in sorted(l.decode.items())}
                    if l.decode else None,
                    "attention": l.attention.to_row() if l.attention else None,
                    "scan": l.scan.to_row() if l.scan else None,
                    "qdtype": l.qdtype,
                    "qerror": l.qerror,
                }
                for l in self.layers
            ],
            indent=2,
        )

    @classmethod
    def from_json(cls, s: str) -> "DataflowPlan":
        plan = cls()
        for row in json.loads(s):
            gemm = GemmShape(M=row["M"], K=row["K"], N=row["N"], name=row["name"])
            blk = row.get("block")
            dec = row.get("decode")
            plan.layers.append(
                LayerPlan(
                    name=row["name"],
                    gemm=gemm,
                    dataflow=Dataflow[row["dataflow"]],
                    est_cost=row["est_cost"],
                    block=tuple(blk) if blk else None,
                    source=row.get("source", "analytical"),
                    strip=int(row.get("strip") or 1),
                    bwd_dx=GemmPlan.from_row(row.get("bwd_dx")),
                    bwd_dw=GemmPlan.from_row(row.get("bwd_dw")),
                    mesh=MeshPlan.from_row(row.get("mesh")),
                    decode={int(b): GemmPlan.from_row(r) for b, r in dec.items()}
                    if dec else None,
                    attention=AttnPlan.from_row(row.get("attention")),
                    scan=ScanPlan.from_row(row.get("scan")),
                    qdtype=row.get("qdtype"),
                    qerror=row.get("qerror"),
                )
            )
        return plan


def plan_systolic(layers: list[ConvLayer | GemmShape], array: int) -> DataflowPlan:
    """The paper's offline search on the cycle model (3 runs per layer)."""
    plan = DataflowPlan()
    for layer in layers:
        gemm = layer.gemm() if isinstance(layer, ConvLayer) else layer
        cycles = {df: systolic_cycles(gemm, df, array, array) for df in ALL_DATAFLOWS}
        best = min(cycles, key=cycles.get)  # type: ignore[arg-type]
        plan.layers.append(
            LayerPlan(name=gemm.name, gemm=gemm, dataflow=best, est_cost=cycles[best])
        )
    return plan


def plan_kernels(
    gemms: list[GemmShape],
    bm: int = 512,
    bk: int = 512,
    bn: int = 512,
    vmem_limit: int = VMEM_BUDGET_BYTES,
) -> DataflowPlan:
    """TPU-native CMU: pick per-GEMM dataflow by HBM-traffic roofline."""
    plan = DataflowPlan()
    for gemm in gemms:
        df, cost = best_kernel_dataflow(gemm, bm=bm, bk=bk, bn=bn, vmem_limit=vmem_limit)
        plan.layers.append(
            LayerPlan(name=gemm.name, gemm=gemm, dataflow=df, est_cost=cost.time_s(),
                      block=(bm, bk, bn))
        )
    return plan


def plan_kernels_tuned(
    gemms: list[GemmShape], vmem_limit: int = VMEM_BUDGET_BYTES
) -> list[tuple[GemmShape, Dataflow, tuple[int, int, int], float]]:
    """Full CMU: co-tuned (dataflow, block) per GEMM. Returns rich rows."""
    rows = []
    for g in gemms:
        df, blk, cost = tune_kernel_dataflow(g, vmem_limit=vmem_limit)
        rows.append((g, df, blk, cost.time_s()))
    return rows


# ---------------------------------------------------------------------------
# Measured autotune — the production CMU
# ---------------------------------------------------------------------------

# Interpret-mode timing on CPU is only meaningful (and affordable) up to this
# many MACs; beyond it autotune_plan keeps the analytical ranking instead.
MAX_INTERPRET_MACS = 64 * 1024 ** 2


def measure_kernel(
    gemm: GemmShape,
    dataflow: Dataflow,
    block: tuple[int, int, int],
    *,
    dtype=None,
    iters: int = 3,
    warmup: int = 1,
    interpret: bool | None = None,
    epilogue: "bool | EpilogueSig" = False,
    trans: tuple[bool, bool] = NO_TRANS,
    via_copy: bool = False,
    strip: int = 1,
    qdtype: str | None = None,
) -> float:
    """Walltime (s) of one real kernel execution of ``gemm`` under
    (dataflow, block, strip) — interpret mode on CPU, on-device on TPU.

    Returns the best of ``iters`` timed runs (min filters scheduler noise).
    Operands are ``dtype`` (default bf16, the width the traffic and VMEM
    models price and the models' compute dtype).
    ``epilogue`` selects what is timed for forward GEMMs: ``False`` the bare
    matmul, ``True`` the legacy bias+gelu probe, or an ``EpilogueSig`` for
    the layer's actual fused signature (so the measurement covers the op the
    model actually issues).

    ``trans`` gives the operand layouts of a backward GEMM: operands are
    *created* transposed ((K, M) / (N, K)) and the transposed-variant kernel
    streams them as stored.  With ``via_copy`` the same transposed operands
    are instead materialised back to plain layout inside the timed region
    before the plain kernel runs — the copy-based fallback, **its HBM
    transpose cost included**, which is what makes the CMU's re-ranking of
    the two variants honest.

    ``strip`` times the WS/IS two-level schedule (VMEM-resident accumulator
    strip); 1 is the streamed schedule.

    ``qdtype`` ("int8" / "fp8") times the weight-quantized variant: the
    per-channel quantize runs inside the timed region (it is part of the
    dispatch) and the kernel streams the 1-byte operand with the fused
    dequant epilogue.  Quantized timing is forward-only (``trans`` must be
    ``NO_TRANS``).
    """
    import time

    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    if interpret is None:
        interpret = ops.default_interpret()
    dtype = dtype or jnp.bfloat16
    sig = _epilogue_sig(epilogue)
    if sig is not None and (trans != NO_TRANS or via_copy):
        raise ValueError(
            "epilogue timing is for forward GEMMs, which never run "
            "transposed — drop epilogue or trans/via_copy"
        )
    if qdtype is not None and (trans != NO_TRANS or via_copy):
        raise ValueError("quantized timing is forward-only (trans=NO_TRANS)")
    trans_a, trans_b = trans
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (gemm.K, gemm.M) if trans_a else (gemm.M, gemm.K),
                          dtype)
    w = jax.random.normal(kw, (gemm.N, gemm.K) if trans_b else (gemm.K, gemm.N),
                          dtype)
    if sig is not None:
        b = jnp.zeros((gemm.N,), dtype) if sig.bias else None
        res = (jnp.zeros((gemm.M, gemm.N), dtype) if sig.residual else None)
        run = lambda: ops.flex_linear(
            x, w, b, activation=sig.activation, residual=res,
            dataflow=dataflow, block=block, interpret=interpret, strip=strip,
            qdtype=qdtype,
        )
    elif via_copy:
        # eager .T executes an HBM transpose copy on every timed call
        run = lambda: ops.flex_matmul(
            x.T if trans_a else x, w.T if trans_b else w,
            dataflow=dataflow, block=block, interpret=interpret, strip=strip,
        )
    else:
        run = lambda: ops.flex_matmul(
            x, w, dataflow=dataflow, block=block, interpret=interpret,
            trans_a=trans_a, trans_b=trans_b, strip=strip, qdtype=qdtype,
        )
    for _ in range(warmup):
        run().block_until_ready()
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        run().block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def bwd_gemms(gemm: GemmShape) -> tuple[GemmShape, GemmShape]:
    """The two cotangent GEMMs of a forward ``C[M,N] = A[M,K] @ B[K,N]``:

      dX = dY @ B^T   — an (M,N)x(N,K) GEMM  (M=M, K=N, N=K)
      dW = A^T @ dY   — a  (K,M)x(M,N) GEMM  (M=K, K=M, N=N)

    Both transpose the forward's aspect ratio, which is why they generally
    land on different dataflows than the forward pass.
    """
    return (
        GemmShape(M=gemm.M, K=gemm.N, N=gemm.K, name=gemm.name + ".dx"),
        GemmShape(M=gemm.K, K=gemm.M, N=gemm.N, name=gemm.name + ".dw"),
    )


# Default accuracy budget for the quant gate: a quantized dtype is only
# eligible when its measured calibration error (relative RMS of the layer's
# output vs full precision) stays under this bound.  int8 per-channel lands
# around 0.8% on Gaussian weights, fp8(e4m3) around 3% — the default admits
# both; tighten it (``--quant-budget`` / ``quant_budget=``) to force int8-only
# or full bf16 fallback.
QUANT_ERROR_BUDGET = 0.05

# Analytical per-operand byte widths of a weight-quantized candidate: the
# activation stays bf16, the weight streams at 1 byte/element, and the
# per-output-channel f32 scale rides the epilogue (folded into the B term of
# the traffic model so stationarity re-fetch factors multiply it).
_QUANT_TRAFFIC = dict(a_bytes=2, b_bytes=1, scale_bytes=4)


def measure_quant_error(gemm: GemmShape, qdtype: str) -> float:
    """Calibration error of quantizing ``gemm``'s weight to ``qdtype``:
    relative RMS of ``x @ dequant(quantize(w))`` against ``x @ w`` on a
    deterministic probe batch (16 rows, weight columns subsampled to 512).

    This is the accuracy gate's oracle — a module global, like
    ``measure_kernel``, so tests can substitute a fake (e.g. force a layer
    over budget and assert the recorded fallback).  Deterministic by
    construction: seeded PRNG, shapes only from ``gemm`` — the same
    (K, N, qdtype) always scores the same error.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels.quantize import dequantize_channel, quantize_channel

    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    n = min(gemm.N, 512)
    x = jax.random.normal(kx, (16, gemm.K), jnp.float32)
    w = jax.random.normal(kw, (gemm.K, n), jnp.float32)
    ref = x @ w
    out = x @ dequantize_channel(*quantize_channel(w, qdtype, axis=0))
    err = jnp.linalg.norm(out - ref) / (jnp.linalg.norm(ref) + 1e-12)
    return float(err)


def _ranked_candidates(
    gemm: GemmShape, vmem_limit: int, quant: tuple[str, ...] = (),
    *, on_chip: bool = False,
) -> list[tuple[float, Dataflow, tuple[int, int, int], int, str | None]]:
    """All VMEM-feasible (dataflow, block, strip) configs, best analytical
    first.

    The strip axis makes the schedule space three-dimensional: for WS/IS
    every accumulator-strip depth that tiles the streamed output axis is a
    distinct schedule (strip=1 streams partials through HBM; deeper strips
    trade stationary-operand re-fetches for zero partial traffic), and the
    strip's f32 scratch counts against the same ``VMEM_BUDGET_BYTES`` as
    the operand blocks — a candidate whose strip doesn't fit is discarded,
    never silently shrunk.  OS contributes strip=1 only (its accumulator is
    already resident; the wider-accumulator OS *is* the IS strip schedule).
    The M-axis candidates include the sublane-aligned skinny blocks so
    decode-geometry GEMMs (M <= 32) are not forced to pad to 128 rows.

    ``quant`` adds a fourth axis: for each qdtype that already passed the
    accuracy gate (callers pre-filter — ranking never decides accuracy),
    every schedule is re-costed with the weight at 1 byte/element plus the
    f32 per-channel scale.  Pass the eligible dtypes sorted by calibration
    error: the sort is stable, so when two 1-byte dtypes tie on traffic the
    lower-error one ranks first.

    ``on_chip`` (the kernels will be compiled by Mosaic, not interpreted)
    leaves out the streamed WS/IS schedules that revisit their partial-sum
    blocks (``revisits_output``): only the interpreter runs them.
    """
    ranked = []
    for df in ALL_DATAFLOWS:
        for bm in kernel_block_candidates(gemm.M, sublane=True):
            for bk in kernel_block_candidates(gemm.K):
                for bn in kernel_block_candidates(gemm.N):
                    for strip in strip_candidates(
                        strip_blocks(gemm, df, bm, bn)
                    ):
                        if on_chip and revisits_output(df, gemm.K, bk, strip):
                            continue
                        for qd in (None, *quant):
                            # explicit per-operand widths — the byte model
                            # must not fall back to a silent dtype default
                            kw = (_QUANT_TRAFFIC if qd
                                  else dict(a_bytes=2, b_bytes=2))
                            cost = hbm_traffic_bytes(gemm, df, bm, bk, bn,
                                                     strip=strip, **kw)
                            if cost.vmem_bytes <= vmem_limit:
                                ranked.append(
                                    (cost.time_s(), cost.hbm_bytes, df,
                                     (bm, bk, bn), strip, qd)
                                )
    # roofline ties (compute-bound shapes) break toward less HBM traffic —
    # same walltime, less bandwidth and energy
    ranked.sort(key=lambda t: (t[0], t[1]))
    return [(t, df, blk, strip, qd) for t, _, df, blk, strip, qd in ranked]


def _tune_gemm(
    gemm: GemmShape,
    *,
    vmem_limit: int,
    top_k: int,
    measure: bool,
    iters: int,
    interpret: bool,
    epilogue: "bool | EpilogueSig",
    trans: tuple[bool, bool] = NO_TRANS,
    quant: tuple[str, ...] = (),
    quant_budget: float | None = None,
) -> GemmPlan:
    """Tune one GEMM: analytical pruning over the (dataflow, block, strip)
    space, then real-execution timing of the ``top_k`` survivors (falls
    back to the analytical winner when the GEMM is too large for
    interpret-mode timing or measurement is off).

    ``trans`` marks a backward GEMM whose operands live in transposed
    layout.  Each surviving (dataflow, block, strip) is then timed
    **twice**: the zero-copy transposed-operand variant, and the copy-based
    fallback with its HBM transpose executed inside the timed region — so
    the ranking sees the transpose traffic the old tuner (which timed
    pre-transposed operands) never saw.  Analytically the zero-copy variant
    strictly dominates (same kernel traffic, minus the copy), so it is the
    pick whenever measurement is off.

    ``quant`` requests weight-quantized candidates ("int8"/"fp8").  The
    accuracy gate runs first — ``measure_quant_error`` scores each dtype
    and only those under ``quant_budget`` (default ``QUANT_ERROR_BUDGET``)
    enter the ranking; the gate runs even under ``measure=False``, because
    accuracy is a numerical property, not a timing one.  When quant was
    requested the returned plan always records a verdict: the winning
    quantized dtype (with its ``qerror``), or ``qdtype="bf16"`` when every
    dtype failed the gate or lost the ranking — so a cached plan can prove
    quant was considered, not merely absent.
    """
    budget = QUANT_ERROR_BUDGET if quant_budget is None else quant_budget
    eligible: tuple[str, ...] = ()
    qerrs: dict[str, float] = {}
    if quant and trans == NO_TRANS:
        qerrs = {qd: measure_quant_error(gemm, qd) for qd in quant}
        eligible = tuple(sorted((qd for qd in quant if qerrs[qd] <= budget),
                                key=lambda qd: qerrs[qd]))
    ranked = _ranked_candidates(gemm, vmem_limit, quant=eligible,
                                on_chip=not interpret)
    if not ranked:
        raise ValueError(f"no (dataflow, block, strip) fits VMEM for {gemm}")
    fallback = "bf16" if quant else None
    measurable = measure and not (interpret and gemm.macs > MAX_INTERPRET_MACS)
    if measurable:
        timed = []
        for _, df, blk, strip, qd in ranked[:top_k]:
            timed.append(
                (measure_kernel(gemm, df, blk, iters=iters, interpret=interpret,
                                epilogue=epilogue, trans=trans, strip=strip,
                                qdtype=qd),
                 trans, df, blk, strip, qd)
            )
            if trans != NO_TRANS:
                timed.append(
                    (measure_kernel(gemm, df, blk, iters=iters,
                                    interpret=interpret, trans=trans,
                                    via_copy=True, strip=strip),
                     NO_TRANS, df, blk, strip, qd)
                )
        cost, tr, df, blk, strip, qd = min(timed, key=lambda t: t[0])
        return GemmPlan(dataflow=df, block=blk, est_cost=cost,
                        source="measured", trans=tr, strip=strip,
                        qdtype=qd or fallback, qerror=qerrs.get(qd))
    cost, df, blk, strip, qd = ranked[0]
    return GemmPlan(dataflow=df, block=blk, est_cost=cost,
                    source="analytical", trans=trans, strip=strip,
                    qdtype=qd or fallback, qerror=qerrs.get(qd))


def mesh_local_gemm(gemm: GemmShape, mesh_df: Dataflow, tp: int,
                    dp: int = 1) -> GemmShape:
    """The *post-collective* per-shard GEMM a mesh dataflow hands the local
    kernel, for a global forward ``C[M,N] = A[M,K] @ B[K,N]`` with tokens
    sharded over ``dp * tp`` chips and the weight K-sharded over ``tp``:

      WS: the all-gather rebuilds the DP group's full token block and the
          local kernel contracts only this chip's K shard — (M/dp, K/tp, N);
      IS: tokens stay put, the gathered weight is whole — (M/(dp*tp), K, N);
      OS: one rotation step's partial GEMM — (M/(dp*tp), K/tp, N).
    """
    M, K, N = gemm.M, gemm.K, gemm.N
    if mesh_df is Dataflow.WS:
        return GemmShape(M // dp, K // tp, N, name=gemm.name + ".shard")
    if mesh_df is Dataflow.IS:
        return GemmShape(M // (dp * tp), K, N, name=gemm.name + ".shard")
    if mesh_df is Dataflow.OS:
        return GemmShape(M // (dp * tp), K // tp, N, name=gemm.name + ".shard")
    raise ValueError(mesh_df)  # pragma: no cover


def mesh_shardable(gemm: GemmShape, tp: int, dp: int = 1) -> bool:
    """Whether the distributed path can run this GEMM at all: the token dim
    must divide the full ``dp * tp`` grid and K the tensor axis (the weight
    arrives K-sharded in every mesh dataflow).  The same predicate gates
    both planning (no mesh sub-plan is emitted for a non-dividing layer)
    and trace-time routing (``models.layers.linear`` falls back cleanly)."""
    return tp > 1 and gemm.M % (dp * tp) == 0 and gemm.K % tp == 0


def _tune_mesh(
    gemm: GemmShape,
    mesh: MeshSpec,
    *,
    train: bool,
    epilogue: "bool | EpilogueSig",
    **tune_kw,
) -> MeshPlan | None:
    """Plan one layer's mesh composition: pick the mesh-level dataflow with
    the analytical ICI model (``best_mesh_dataflow`` — CPU cannot measure
    ICI, so this level stays shape-driven, exactly the paper's offline
    argument), then tune the local per-shard kernel geometry for the
    post-collective shapes with the full measured chip-level CMU.

    Only mesh-IS keeps the fused epilogue in-kernel (the gathered weight
    makes the local GEMM the whole layer); WS/OS apply it post-reduction,
    so their local candidates are timed bare.  Returns None when the layer
    doesn't divide the mesh (``mesh_shardable``) — the dispatch then falls
    back to the single-device plan row.
    """
    tp, dp = mesh.tp, mesh.dp
    if not mesh_shardable(gemm, tp, dp):
        return None
    per_dp = GemmShape(gemm.M // dp, gemm.K, gemm.N, name=gemm.name)
    mesh_df, cost = best_mesh_dataflow(per_dp, tp)
    local_shape = mesh_local_gemm(gemm, mesh_df, tp, dp)
    local = _tune_gemm(
        local_shape,
        epilogue=epilogue if mesh_df is Dataflow.IS else False,
        **tune_kw,
    )
    dx = dw = None
    if train:
        g_dx, g_dw = bwd_gemms(local_shape)
        dx = _tune_gemm(g_dx, epilogue=False, trans=TRANS_DX, **tune_kw)
        dw = _tune_gemm(g_dw, epilogue=False, trans=TRANS_DW, **tune_kw)
    return MeshPlan(
        dataflow=mesh_df, axis=mesh.tensor_axis, tp=tp, dp=dp,
        local=local, local_dx=dx, local_dw=dw, comm_bytes=cost.comm_bytes,
    )


def _tune_decode(
    gemm: GemmShape,
    buckets: tuple[int, ...],
    *,
    epilogue: "bool | EpilogueSig" = False,
    **tune_kw,
) -> dict[int, GemmPlan]:
    """Tune one layer's decode sub-plans: the same (K, N) projection at
    M = bucket rows for every serving batch-size bucket, timed with the
    layer's fused-epilogue signature (decode issues the same fused op as
    prefill, just skinny)."""
    out = {}
    for b in sorted(set(buckets)):
        g = GemmShape(M=b, K=gemm.K, N=gemm.N, name=f"{gemm.name}@b{b}")
        out[b] = _tune_gemm(g, epilogue=epilogue, **tune_kw)
    return out


def measure_attention(
    shape: AttnShape,
    sweep: str,
    block: tuple[int, int],
    *,
    dtype=None,
    iters: int = 3,
    warmup: int = 1,
    interpret: bool | None = None,
) -> float:
    """Walltime (s) of one real prefill flash-attention execution of
    ``shape`` under (sweep, (bq, bk)) — the attention analogue of
    ``measure_kernel``, and like it a module global so tests can substitute
    a fake timer."""
    import time

    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels.flash_attention import mha_flash

    if interpret is None:
        interpret = ops.default_interpret()
    dtype = dtype or jnp.bfloat16
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (1, shape.seq, shape.heads, shape.head_dim), dtype)
    k = jax.random.normal(kk, (1, shape.kv, shape.kv_heads, shape.head_dim), dtype)
    v = jax.random.normal(kv, (1, shape.kv, shape.kv_heads, shape.head_dim), dtype)
    bq, bk = block
    run = lambda: mha_flash(q, k, v, causal=True, interpret=interpret,
                            block_q=bq, block_k=bk, sweep=sweep)
    for _ in range(warmup):
        run().block_until_ready()
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        run().block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def measure_attention_decode(
    shape: AttnShape,
    bucket: int,
    kind: str,
    *,
    block_size: int = 16,
    dtype=None,
    iters: int = 3,
    warmup: int = 1,
    interpret: bool | None = None,
) -> float:
    """Walltime (s) of one bucketed decode-attention step over a proxy paged
    cache: ``kind="paged"`` times the in-place Pallas block-table kernel,
    ``kind="gather"`` the pure-jnp densify baseline — both jitted, so the
    ranking compares the dispatches the serve scheduler would issue."""
    import time

    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels.flash_attention import (
        paged_attention,
        paged_attention_reference,
    )

    if interpret is None:
        interpret = ops.default_interpret()
    dtype = dtype or jnp.bfloat16
    cache_len = max(min(shape.kv, 64), block_size)
    nb = -(-cache_len // block_size)
    kq, kp = jax.random.split(jax.random.PRNGKey(0))
    q = jax.random.normal(kq, (bucket, shape.heads, shape.head_dim), dtype)
    pools = jax.random.normal(
        kp, (2, bucket * nb + 1, block_size, shape.kv_heads * shape.head_dim),
        dtype)
    table = 1 + jnp.arange(bucket * nb, dtype=jnp.int32).reshape(bucket, nb)
    positions = jnp.full((bucket,), cache_len - 1, jnp.int32)
    if kind == "paged":
        run = jax.jit(lambda a, k_, v_, t, p: paged_attention(
            a, k_, v_, t, p, interpret=interpret))
    elif kind == "gather":
        run = jax.jit(paged_attention_reference)
    else:
        raise ValueError(f"unknown decode attention kind {kind!r}")
    args = (q, pools[0], pools[1], table, positions)
    for _ in range(warmup):
        run(*args).block_until_ready()
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        run(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def _attn_block_candidates(d: int) -> list[int]:
    """(bq, bk) candidates covering one attention grid axis of extent ``d``:
    the standard tile ladder up to the rounded extent, plus the
    sublane-aligned exact fit when the axis is smaller than one tile (smoke
    prefills, decode-folded rows)."""
    rounded = max(-(-d // 128) * 128, 128)
    cs = {c for c in ATTN_BLOCK_CANDIDATES if c <= rounded}
    small = max(-(-d // 8) * 8, 8)
    if small < 128:
        cs.add(small)
    return sorted(cs)


def _tune_attention(
    shape: AttnShape,
    buckets: tuple[int, ...] | None = None,
    *,
    vmem_limit: int,
    top_k: int,
    measure: bool,
    iters: int,
    interpret: bool,
    **_ignored,
) -> AttnPlan:
    """Tune the flash-attention schedule for one model shape: analytical
    pruning over (sweep, bq, bk) under the VMEM budget — the same
    analytical-rank → timed-execution flow as ``_tune_gemm`` — then
    per-bucket decode-kind tuning (``_tune_attn_decode``) when serving
    buckets are requested."""
    ranked = []
    seen = set()
    for sweep in ATTN_SWEEPS:
        for bq in _attn_block_candidates(shape.rows):
            for bk in _attn_block_candidates(shape.kv):
                # dedup schedules that clamp to the same effective geometry
                eff = (sweep, min(bq, max(-(-shape.rows // 8) * 8, 8)),
                       min(bk, max(-(-shape.kv // 8) * 8, 8)))
                if eff in seen:
                    continue
                seen.add(eff)
                # explicit widths: attention streams bf16 activations + KV
                # cache (weight quantization never touches these operands)
                cost = attn_traffic_bytes(shape, sweep, bq, bk,
                                          in_bytes=2, out_bytes=2)
                if cost.vmem_bytes <= vmem_limit:
                    ranked.append(
                        (cost.time_s(), cost.hbm_bytes, sweep, (bq, bk)))
    if not ranked:
        raise ValueError(f"no attention schedule fits VMEM for {shape}")
    ranked.sort(key=lambda t: (t[0], t[1]))
    measurable = measure and not (interpret and shape.macs > MAX_INTERPRET_MACS)
    if measurable:
        timed = [
            (measure_attention(shape, sweep, blk, iters=iters,
                               interpret=interpret), sweep, blk)
            for _, _, sweep, blk in ranked[:top_k]
        ]
        cost, sweep, blk = min(timed, key=lambda t: t[0])
        plan = AttnPlan(sweep=sweep, block=blk, est_cost=cost,
                        source="measured")
    else:
        cost, _, sweep, blk = ranked[0]
        plan = AttnPlan(sweep=sweep, block=blk, est_cost=cost,
                        source="analytical")
    if buckets:
        import dataclasses

        plan = dataclasses.replace(
            plan, decode=_tune_attn_decode(
                shape, tuple(buckets), measure=measure, iters=iters,
                interpret=interpret, vmem_limit=vmem_limit))
    return plan


def _tune_attn_decode(
    shape: AttnShape,
    buckets: tuple[int, ...],
    *,
    measure: bool,
    iters: int,
    interpret: bool,
    vmem_limit: int = VMEM_BUDGET_BYTES,
    **_ignored,
) -> dict[int, AttnPlan]:
    """Pick the decode-attention kind (paged Pallas kernel vs pure-jnp
    gather) per serving bucket: analytical HBM ranking — the gather's 3x
    cache traffic makes "paged" the analytical default — then timed
    execution of both kinds when measurement is on."""
    out = {}
    for b in sorted(set(buckets)):
        ranked = []
        for kind in ATTN_DECODE_KINDS:
            cost = attn_decode_traffic_bytes(shape, kind, b,
                                             in_bytes=2, out_bytes=2)
            if cost.vmem_bytes <= vmem_limit:
                ranked.append((cost.time_s(), cost.hbm_bytes, kind))
        ranked.sort(key=lambda t: (t[0], t[1]))
        if measure:
            timed = [
                (measure_attention_decode(shape, b, kind, iters=iters,
                                          interpret=interpret), kind)
                for _, _, kind in ranked
            ]
            cost, kind = min(timed, key=lambda t: t[0])
            out[b] = AttnPlan(sweep=kind, block=(), est_cost=cost,
                              source="measured")
        else:
            cost, _, kind = ranked[0]
            out[b] = AttnPlan(sweep=kind, block=(), est_cost=cost,
                              source="analytical")
    return out


def _scan_inputs(shape: ScanShape, seq: int, dtype):
    """Random (r, k, v, log_w, u) probe operands for one scan timing run —
    log_w drawn in the clipped [LOG_DECAY_MIN, -1e-6] band the models
    produce, u only for the RWKV (pre-update) convention."""
    import jax
    import jax.numpy as jnp

    from repro.models.ssm import LOG_DECAY_MIN

    B, H = shape.batch, shape.heads
    n, m = shape.key_dim, shape.val_dim
    kr, kk, kv, kw, ku = jax.random.split(jax.random.PRNGKey(0), 5)
    r = jax.random.normal(kr, (B, seq, H, n), dtype)
    k = jax.random.normal(kk, (B, seq, H, n), dtype)
    v = jax.random.normal(kv, (B, seq, H, m), dtype)
    lw = jnp.clip(
        -jax.nn.softplus(jax.random.normal(kw, (B, seq, H, n))),
        LOG_DECAY_MIN, -1e-6).astype(jnp.float32)
    u = (None if shape.post_update
         else jax.random.normal(ku, (H, n), jnp.float32) * 0.5)
    return r, k, v, lw, u


def measure_scan(
    shape: ScanShape,
    sweep: str,
    chunk: int,
    *,
    dtype=None,
    iters: int = 3,
    warmup: int = 1,
    interpret: bool | None = None,
) -> float:
    """Walltime (s) of one real prefill chunked-scan execution of ``shape``
    under (sweep, chunk) — the scan analogue of ``measure_attention``, and
    like it a module global so tests can substitute a fake timer."""
    import time

    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels.flex_scan import flex_scan

    if interpret is None:
        interpret = ops.default_interpret()
    dtype = dtype or jnp.bfloat16
    seq = -(-shape.seq // chunk) * chunk  # the padded T the model dispatches
    r, k, v, lw, u = _scan_inputs(shape, seq, dtype)
    run = lambda: flex_scan(r, k, v, lw, u, chunk=chunk, sweep=sweep,
                            post_update=shape.post_update,
                            interpret=interpret)[0]
    for _ in range(warmup):
        run().block_until_ready()
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        run().block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def measure_scan_decode(
    shape: ScanShape,
    bucket: int,
    kind: str,
    *,
    dtype=None,
    iters: int = 3,
    warmup: int = 1,
    interpret: bool | None = None,
) -> float:
    """Walltime (s) of one bucketed decode-scan step: ``kind="fused"`` times
    the single Pallas step kernel, ``kind="einsum"`` the jnp recurrence —
    both jitted, so the ranking compares the dispatches the decode step
    would issue."""
    import time

    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels.flex_scan import flex_recurrent_step
    from repro.models.ssm import recurrent_step

    if interpret is None:
        interpret = ops.default_interpret()
    dtype = dtype or jnp.bfloat16
    bshape = ScanShape(batch=bucket, seq=1, heads=shape.heads,
                       key_dim=shape.key_dim, val_dim=shape.val_dim,
                       post_update=shape.post_update)
    r, k, v, lw, u = _scan_inputs(bshape, 1, dtype)
    r, k, v, lw = r[:, 0], k[:, 0], v[:, 0], lw[:, 0]
    S = jnp.zeros((bucket, shape.heads, shape.key_dim, shape.val_dim),
                  jnp.float32)
    if kind == "fused":
        run = jax.jit(lambda *a: flex_recurrent_step(
            *a, post_update=shape.post_update, interpret=interpret)[0])
    elif kind == "einsum":
        run = jax.jit(lambda *a: recurrent_step(
            *a, post_update=shape.post_update)[0])
    else:
        raise ValueError(f"unknown decode scan kind {kind!r}")
    args = (r, k, v, lw, S, u)
    for _ in range(warmup):
        run(*args).block_until_ready()
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        run(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def _tune_scan(
    shape: ScanShape,
    buckets: tuple[int, ...] | None = None,
    *,
    vmem_limit: int,
    top_k: int,
    measure: bool,
    iters: int,
    interpret: bool,
    **_ignored,
) -> ScanPlan:
    """Tune the chunked-scan schedule for one model shape: analytical
    pruning over (sweep, chunk) under the VMEM budget — the same
    analytical-rank → timed-execution flow as ``_tune_attention`` — then
    per-bucket decode-kind tuning (``_tune_scan_decode``) when serving
    buckets are requested."""
    ranked = []
    seen = set()
    # the "out" sweep revisits its state blocks non-consecutively, which
    # only the interpreter runs (see ``dataflow.revisits_output``)
    sweeps = SCAN_SWEEPS if interpret else ("state",)
    for sweep in sweeps:
        for chunk in SCAN_CHUNK_CANDIDATES:
            # dedup schedules whose padded grid collapses to one chunk
            eff = (sweep, min(chunk, -(-shape.seq // 8) * 8))
            if eff in seen:
                continue
            seen.add(eff)
            # explicit widths: the scan streams bf16 activations/state
            cost = scan_traffic_bytes(shape, sweep, chunk,
                                      in_bytes=2, out_bytes=2)
            if cost.vmem_bytes <= vmem_limit:
                ranked.append((cost.time_s(), cost.hbm_bytes, sweep, chunk))
    if not ranked:
        raise ValueError(f"no scan schedule fits VMEM for {shape}")
    ranked.sort(key=lambda t: (t[0], t[1]))
    measurable = measure and not (interpret and shape.macs > MAX_INTERPRET_MACS)
    if measurable:
        timed = [
            (measure_scan(shape, sweep, chunk, iters=iters,
                          interpret=interpret), sweep, chunk)
            for _, _, sweep, chunk in ranked[:top_k]
        ]
        cost, sweep, chunk = min(timed, key=lambda t: t[0])
        plan = ScanPlan(sweep=sweep, chunk=chunk, est_cost=cost,
                        source="measured")
    else:
        cost, _, sweep, chunk = ranked[0]
        plan = ScanPlan(sweep=sweep, chunk=chunk, est_cost=cost,
                        source="analytical")
    if buckets:
        import dataclasses

        plan = dataclasses.replace(
            plan, decode=_tune_scan_decode(
                shape, tuple(buckets), measure=measure, iters=iters,
                interpret=interpret, vmem_limit=vmem_limit))
    return plan


def _tune_scan_decode(
    shape: ScanShape,
    buckets: tuple[int, ...],
    *,
    measure: bool,
    iters: int,
    interpret: bool,
    vmem_limit: int = VMEM_BUDGET_BYTES,
    **_ignored,
) -> dict[int, ScanPlan]:
    """Pick the decode-scan kind (fused Pallas step kernel vs jnp
    recurrence) per serving bucket: analytical HBM ranking — the jnp path's
    materialized k v^T intermediate makes "fused" the analytical default —
    then timed execution of both kinds when measurement is on."""
    out = {}
    for b in sorted(set(buckets)):
        ranked = []
        for kind in SCAN_DECODE_KINDS:
            cost = scan_decode_traffic_bytes(shape, kind, b,
                                             in_bytes=2, out_bytes=2)
            if cost.vmem_bytes <= vmem_limit:
                ranked.append((cost.time_s(), cost.hbm_bytes, kind))
        ranked.sort(key=lambda t: (t[0], t[1]))
        if measure:
            timed = [
                (measure_scan_decode(shape, b, kind, iters=iters,
                                     interpret=interpret), kind)
                for _, _, kind in ranked
            ]
            cost, kind = min(timed, key=lambda t: t[0])
            out[b] = ScanPlan(sweep=kind, chunk=0, est_cost=cost,
                              source="measured")
        else:
            cost, _, kind = ranked[0]
            out[b] = ScanPlan(sweep=kind, chunk=0, est_cost=cost,
                              source="analytical")
    return out


def autotune_plan(
    gemms: list[GemmShape],
    *,
    vmem_limit: int = VMEM_BUDGET_BYTES,
    top_k: int = 3,
    measure: bool = True,
    iters: int = 2,
    interpret: bool | None = None,
    epilogue: "bool | EpilogueSig | dict[str, EpilogueSig | None]" = False,
    train: bool = False,
    mesh: MeshSpec | None = None,
    decode_buckets: tuple[int, ...] | None = None,
    attn: AttnShape | None = None,
    scan: ScanShape | None = None,
    quant: tuple[str, ...] | None = None,
    quant_budget: float | None = None,
) -> DataflowPlan:
    """Measured-autotune CMU: analytical pruning + real-execution timing.

    Per GEMM: rank every VMEM-feasible (dataflow, block, strip) config with
    the strip-aware roofline model — WS/IS accumulator-strip depths are
    schedules in their own right, trading stationary-operand re-fetches for
    zero partial-sum HBM traffic under one shared ``VMEM_BUDGET_BYTES`` —
    keep the ``top_k`` best, time each survivor with real kernel
    executions, and program the walltime argmin into the plan.  When
    measurement is disabled (or the GEMM is too large for interpret-mode
    timing on CPU) the analytical winner is kept, marked
    ``source="analytical"`` so callers can tell which decisions were measured.

    ``epilogue`` makes the forward measurements epilogue-aware: a bool
    applies the same probe to every layer (legacy), while a dict maps layer
    names to each layer's actual ``EpilogueSig`` — the serve/train drivers
    pass ``model_epilogues(cfg)`` so every candidate is timed as the fused
    op the model issues, not the bare matmul.

    With ``train=True`` each layer is planned as a **group of three GEMMs**:
    the forward plus its two cotangent GEMMs (``bwd_gemms``).  The backward
    sub-GEMMs are tuned over *both* operand layouts — the zero-copy
    transposed-variant kernels and the copy-based fallback with its
    transpose cost included (see ``_tune_gemm``) — and land in
    ``LayerPlan.bwd_dx`` / ``bwd_dw`` with their winning ``trans``.

    With ``mesh`` (a ``MeshSpec`` fingerprint) every layer additionally
    gets a **mesh sub-plan** (``_tune_mesh``): the mesh-level stationarity
    from the analytical ICI model plus the local per-shard kernel geometry
    tuned for the post-collective shapes.  The single-device decisions
    above are still tuned for the global geometry — they remain the
    dispatch for layers the mesh can't divide.

    With ``decode_buckets`` every layer additionally gets per-bucket
    **decode sub-plans** (``_tune_decode``): the same projection tuned at
    M = bucket rows for each serving batch-size bucket, so a
    continuous-batching decode step dispatches a skinny-bm geometry keyed
    on its quantized live batch instead of the prefill-sized forward row.

    With ``attn`` (the model's ``AttnShape``) the ``ATTN_ANCHOR`` row
    additionally carries an **attention schedule** (``_tune_attention``):
    the flash-kernel sweep order and (bq, bk) blocks for prefill, plus —
    when ``decode_buckets`` is also given — the per-bucket decode-attention
    kind (paged Pallas kernel vs jnp gather), all under the same
    analytical-pruning → timed-execution flow and VMEM budget.

    With ``scan`` (the model's ``ScanShape``) the ``SCAN_ANCHOR`` row
    additionally carries a **chunked-scan schedule** (``_tune_scan``): the
    state-residency sweep and chunk length for SSM/RWKV prefill, plus —
    when ``decode_buckets`` is also given — the per-bucket decode-scan
    kind (fused Pallas step kernel vs jnp recurrence), under the same
    flow and budget as attention.

    With ``quant`` (a tuple of "int8"/"fp8") the forward rows and decode
    sub-plans additionally search **weight-quantized candidates**: each
    requested dtype is accuracy-gated per layer (``measure_quant_error``
    vs ``quant_budget``, default ``QUANT_ERROR_BUDGET``) before entering
    the ranking, and every row records its verdict in ``qdtype`` /
    ``qerror`` — a quantized winner, or "bf16" when quant lost or failed
    the gate.  Backward and mesh sub-plans never quantize: gradients flow
    through the saved full-precision weight (straight-through), and the
    sharded dispatch has no quantized path.
    """
    if interpret is None:
        from repro.kernels import ops

        interpret = ops.default_interpret()
    kw = dict(vmem_limit=vmem_limit, top_k=top_k, measure=measure,
              iters=iters, interpret=interpret)
    qkw = dict(quant=tuple(quant or ()), quant_budget=quant_budget)
    plan = DataflowPlan(mesh=mesh)
    for gemm in gemms:
        sig = epilogue.get(gemm.name) if isinstance(epilogue, dict) else epilogue
        fwd = _tune_gemm(gemm, epilogue=sig or False, **qkw, **kw)
        dx = dw = None
        if train:
            g_dx, g_dw = bwd_gemms(gemm)
            dx = _tune_gemm(g_dx, epilogue=False, trans=TRANS_DX, **kw)
            dw = _tune_gemm(g_dw, epilogue=False, trans=TRANS_DW, **kw)
        mp = None
        if mesh is not None:
            mp = _tune_mesh(gemm, mesh, train=train, epilogue=sig or False,
                            **kw)
        dec = None
        if decode_buckets:
            dec = _tune_decode(gemm, tuple(decode_buckets),
                               epilogue=sig or False, **qkw, **kw)
        ap = None
        if attn is not None and gemm.name == ATTN_ANCHOR:
            ap = _tune_attention(attn, tuple(decode_buckets or ()) or None,
                                 **kw)
        sp = None
        if scan is not None and gemm.name == SCAN_ANCHOR:
            sp = _tune_scan(scan, tuple(decode_buckets or ()) or None, **kw)
        plan.layers.append(
            LayerPlan(name=gemm.name, gemm=gemm, dataflow=fwd.dataflow,
                      est_cost=fwd.est_cost, block=fwd.block, source=fwd.source,
                      bwd_dx=dx, bwd_dw=dw, strip=fwd.strip, mesh=mp,
                      decode=dec, attention=ap, scan=sp,
                      qdtype=fwd.qdtype, qerror=fwd.qerror)
        )
    return plan


def add_mesh_subplans(
    plan: DataflowPlan,
    mesh: MeshSpec,
    *,
    train: bool = False,
    epilogue: "bool | EpilogueSig | dict[str, EpilogueSig | None]" = False,
    vmem_limit: int = VMEM_BUDGET_BYTES,
    top_k: int = 3,
    measure: bool = True,
    iters: int = 2,
    interpret: bool | None = None,
    **_ignored,
) -> DataflowPlan:
    """Upgrade a plan for a (new) mesh **incrementally**: every
    single-device decision — forward rows and backward sub-plans — is kept
    verbatim (so a migrated v1–v4 cache keeps dispatching bit-for-bit on
    layers that fall back), and only the mesh sub-plans are (re)tuned for
    ``mesh``'s post-collective shapes."""
    import dataclasses

    if interpret is None:
        from repro.kernels import ops

        interpret = ops.default_interpret()
    kw = dict(vmem_limit=vmem_limit, top_k=top_k, measure=measure,
              iters=iters, interpret=interpret)
    out = DataflowPlan(mesh=mesh)
    for l in plan.layers:
        sig = epilogue.get(l.name) if isinstance(epilogue, dict) else epilogue
        mp = _tune_mesh(l.gemm, mesh, train=train, epilogue=sig or False, **kw)
        out.layers.append(dataclasses.replace(l, mesh=mp))
    return out


def add_bwd_subplans(
    plan: DataflowPlan,
    *,
    vmem_limit: int = VMEM_BUDGET_BYTES,
    top_k: int = 3,
    measure: bool = True,
    iters: int = 2,
    interpret: bool | None = None,
    **_ignored,
) -> DataflowPlan:
    """Upgrade a forward-only plan for training **incrementally**: keep every
    already-tuned forward decision (measurements are expensive) and tune only
    the missing dX/dW sub-GEMMs.  Layers that already carry both sub-plans
    are passed through untouched, and the plan's mesh fingerprint (plus any
    per-layer mesh sub-plans) is preserved."""
    import dataclasses

    if interpret is None:
        from repro.kernels import ops

        interpret = ops.default_interpret()
    kw = dict(vmem_limit=vmem_limit, top_k=top_k, measure=measure,
              iters=iters, interpret=interpret, epilogue=False)
    out = DataflowPlan(mesh=plan.mesh)
    for l in plan.layers:
        if l.bwd_dx is not None and l.bwd_dw is not None:
            out.layers.append(l)
            continue
        g_dx, g_dw = bwd_gemms(l.gemm)
        out.layers.append(dataclasses.replace(
            l, bwd_dx=_tune_gemm(g_dx, trans=TRANS_DX, **kw),
            bwd_dw=_tune_gemm(g_dw, trans=TRANS_DW, **kw)
        ))
    return out


def add_decode_subplans(
    plan: DataflowPlan,
    buckets: tuple[int, ...],
    *,
    epilogue: "bool | EpilogueSig | dict[str, EpilogueSig | None]" = False,
    vmem_limit: int = VMEM_BUDGET_BYTES,
    top_k: int = 3,
    measure: bool = True,
    iters: int = 2,
    interpret: bool | None = None,
    **_ignored,
) -> DataflowPlan:
    """Upgrade a plan for bucketed serving **incrementally**: every existing
    decision — forward rows, backward and mesh sub-plans, and decode buckets
    already tuned — is kept verbatim (a migrated v1–v5 cache keeps
    dispatching bit-for-bit everywhere else), and only the missing decode
    buckets are tuned."""
    import dataclasses

    if interpret is None:
        from repro.kernels import ops

        interpret = ops.default_interpret()
    kw = dict(vmem_limit=vmem_limit, top_k=top_k, measure=measure,
              iters=iters, interpret=interpret)
    out = DataflowPlan(mesh=plan.mesh)
    want = tuple(sorted(set(buckets)))
    for l in plan.layers:
        have = dict(l.decode or {})
        missing = tuple(b for b in want if b not in have)
        if not missing:
            out.layers.append(l)
            continue
        sig = epilogue.get(l.name) if isinstance(epilogue, dict) else epilogue
        have.update(_tune_decode(l.gemm, missing, epilogue=sig or False, **kw))
        out.layers.append(dataclasses.replace(l, decode=have))
    return out


def add_attention_subplans(
    plan: DataflowPlan,
    attn: AttnShape,
    buckets: tuple[int, ...] | None = None,
    *,
    vmem_limit: int = VMEM_BUDGET_BYTES,
    top_k: int = 3,
    measure: bool = True,
    iters: int = 2,
    interpret: bool | None = None,
    **_ignored,
) -> DataflowPlan:
    """Upgrade a plan with an attention schedule **incrementally**: every
    existing decision — forward rows, backward/mesh/decode sub-plans, and
    any attention schedule already tuned — is kept verbatim (a migrated
    v1–v6 cache keeps dispatching bit-for-bit everywhere else), and only
    the missing attention pieces (the prefill schedule, or just the decode
    buckets a wider run added) are tuned."""
    import dataclasses

    if interpret is None:
        from repro.kernels import ops

        interpret = ops.default_interpret()
    kw = dict(vmem_limit=vmem_limit, top_k=top_k, measure=measure,
              iters=iters, interpret=interpret)
    want = tuple(sorted(set(buckets or ())))
    out = DataflowPlan(mesh=plan.mesh)
    for l in plan.layers:
        if l.name != ATTN_ANCHOR:
            out.layers.append(l)
            continue
        ap = l.attention
        if ap is None:
            ap = _tune_attention(attn, want or None, **kw)
        else:
            have = dict(ap.decode or {})
            missing = tuple(b for b in want if b not in have)
            if missing:
                have.update(_tune_attn_decode(attn, missing, **kw))
                ap = dataclasses.replace(ap, decode=have)
        out.layers.append(dataclasses.replace(l, attention=ap))
    return out


def add_scan_subplans(
    plan: DataflowPlan,
    scan: ScanShape,
    buckets: tuple[int, ...] | None = None,
    *,
    vmem_limit: int = VMEM_BUDGET_BYTES,
    top_k: int = 3,
    measure: bool = True,
    iters: int = 2,
    interpret: bool | None = None,
    **_ignored,
) -> DataflowPlan:
    """Upgrade a plan with a chunked-scan schedule **incrementally**: every
    existing decision — forward rows, backward/mesh/decode/attention
    sub-plans, and any scan schedule already tuned — is kept verbatim (a
    migrated v1–v7 cache keeps dispatching bit-for-bit everywhere else),
    and only the missing scan pieces (the prefill schedule, or just the
    decode buckets a wider run added) are tuned."""
    import dataclasses

    if interpret is None:
        from repro.kernels import ops

        interpret = ops.default_interpret()
    kw = dict(vmem_limit=vmem_limit, top_k=top_k, measure=measure,
              iters=iters, interpret=interpret)
    want = tuple(sorted(set(buckets or ())))
    out = DataflowPlan(mesh=plan.mesh)
    for l in plan.layers:
        if l.name != SCAN_ANCHOR:
            out.layers.append(l)
            continue
        sp = l.scan
        if sp is None:
            sp = _tune_scan(scan, want or None, **kw)
        else:
            have = dict(sp.decode or {})
            missing = tuple(b for b in want if b not in have)
            if missing:
                have.update(_tune_scan_decode(scan, missing, **kw))
                sp = dataclasses.replace(sp, decode=have)
        out.layers.append(dataclasses.replace(l, scan=sp))
    return out


def _quant_choice(
    gemm: GemmShape,
    dataflow: Dataflow,
    block: tuple[int, int, int] | None,
    strip: int,
    *,
    quant: tuple[str, ...],
    budget: float,
    measure: bool,
    iters: int,
    interpret: bool,
    epilogue: "bool | EpilogueSig" = False,
) -> tuple[str, float | None]:
    """Decide the qdtype for an **already-tuned geometry**: the incremental
    upgrade's analogue of ``_tune_gemm``'s quant axis.  The accuracy gate
    runs first; surviving dtypes are then compared against the unquantized
    dispatch at the *same* (dataflow, block, strip) — timed when
    measurement is on, by the dtype-aware traffic model otherwise — so the
    upgrade never perturbs a cached schedule decision, only annotates it.
    Returns ``(qdtype, qerror)`` with "bf16" when everything fails the gate
    or loses."""
    qerrs = {qd: measure_quant_error(gemm, qd) for qd in quant}
    eligible = sorted((qd for qd in quant if qerrs[qd] <= budget),
                      key=lambda qd: qerrs[qd])
    if not eligible:
        return "bf16", None
    blk = block or (256, 256, 256)  # kernels' DEFAULT_BLOCK
    measurable = measure and not (interpret and gemm.macs > MAX_INTERPRET_MACS)
    if measurable:
        timed = [
            (measure_kernel(gemm, dataflow, blk, iters=iters,
                            interpret=interpret, epilogue=epilogue,
                            strip=strip, qdtype=qd), qd)
            for qd in (None, *eligible)
        ]
        _, qd = min(timed, key=lambda t: t[0])
    else:
        bm, bk, bn = blk
        base = hbm_traffic_bytes(gemm, dataflow, bm, bk, bn, strip=strip,
                                 a_bytes=2, b_bytes=2)
        qcost = hbm_traffic_bytes(gemm, dataflow, bm, bk, bn, strip=strip,
                                  **_QUANT_TRAFFIC)
        better = (qcost.time_s(), qcost.hbm_bytes) < (base.time_s(),
                                                      base.hbm_bytes)
        qd = eligible[0] if better else None
    return (qd or "bf16"), qerrs.get(qd)


def add_quant_subplans(
    plan: DataflowPlan,
    quant: tuple[str, ...],
    *,
    quant_budget: float | None = None,
    epilogue: "bool | EpilogueSig | dict[str, EpilogueSig | None]" = False,
    vmem_limit: int = VMEM_BUDGET_BYTES,
    top_k: int = 3,
    measure: bool = True,
    iters: int = 2,
    interpret: bool | None = None,
    **_ignored,
) -> DataflowPlan:
    """Upgrade a plan with quant verdicts **incrementally**: every existing
    decision — forward (dataflow, block, strip, trans, est_cost), backward,
    mesh, decode, attention and scan sub-plans — is kept **verbatim** (a
    migrated v1–v8 cache keeps dispatching bit-for-bit), and only the
    missing ``qdtype`` / ``qerror`` annotations are decided: per forward
    row and per decode bucket, each at its already-tuned geometry
    (``_quant_choice``).  Rows that already carry a verdict are passed
    through untouched, so re-running with the same dtypes is a no-op."""
    import dataclasses

    if interpret is None:
        from repro.kernels import ops

        interpret = ops.default_interpret()
    del vmem_limit, top_k  # geometry is frozen — nothing to re-search
    kw = dict(quant=tuple(quant), measure=measure, iters=iters,
              interpret=interpret,
              budget=QUANT_ERROR_BUDGET if quant_budget is None
              else quant_budget)
    out = DataflowPlan(mesh=plan.mesh)
    for l in plan.layers:
        sig = epilogue.get(l.name) if isinstance(epilogue, dict) else epilogue
        new = l
        if l.qdtype is None:
            qd, qe = _quant_choice(l.gemm, l.dataflow, l.block, l.strip,
                                   epilogue=sig or False, **kw)
            new = dataclasses.replace(new, qdtype=qd, qerror=qe)
        if new.decode and any(gp.qdtype is None for gp in new.decode.values()):
            dec = {}
            for b, gp in new.decode.items():
                if gp.qdtype is None:
                    g = GemmShape(M=b, K=l.gemm.K, N=l.gemm.N,
                                  name=f"{l.gemm.name}@b{b}")
                    qd, qe = _quant_choice(g, gp.dataflow, gp.block, gp.strip,
                                           epilogue=sig or False, **kw)
                    gp = dataclasses.replace(gp, qdtype=qd, qerror=qe)
                dec[b] = gp
            new = dataclasses.replace(new, decode=dec)
        out.layers.append(new)
    return out


def model_gemms(cfg, tokens: int) -> list[GemmShape]:
    """The per-layer GEMMs an LM config issues for one batch of ``tokens``.

    Names match the ``name=`` keys ``models.layers.linear`` looks up, so one
    autotuned plan drives every projection in the stack.
    """
    D = cfg.d_model
    gemms = [
        GemmShape(M=tokens, K=D, N=cfg.q_dim, name="attn.wq"),
        GemmShape(M=tokens, K=D, N=cfg.kv_dim, name="attn.wk"),
        GemmShape(M=tokens, K=D, N=cfg.kv_dim, name="attn.wv"),
        GemmShape(M=tokens, K=cfg.q_dim, N=D, name="attn.wo"),
    ]
    if cfg.d_ff:
        gemms += [
            GemmShape(M=tokens, K=D, N=cfg.d_ff, name="mlp.w1"),
            GemmShape(M=tokens, K=cfg.d_ff, N=D, name="mlp.w2"),
        ]
        if cfg.activation in ("silu", "gelu"):
            gemms.append(GemmShape(M=tokens, K=D, N=cfg.d_ff, name="mlp.w3"))
    gemms.append(GemmShape(M=tokens, K=D, N=cfg.padded_vocab, name="lm_head"))
    return gemms


def model_attn_shape(cfg, tokens: int) -> AttnShape:
    """The self-attention planning fingerprint an LM config issues for one
    batch of ``tokens`` — the companion of ``model_gemms`` for the
    ``ATTN_ANCHOR`` row's attention schedule."""
    return AttnShape(
        seq=tokens,
        kv=tokens,
        heads=cfg.num_heads,
        kv_heads=cfg.num_kv_heads or cfg.num_heads,
        head_dim=cfg.head_dim,
    )


def model_scan_shape(cfg, tokens: int) -> "ScanShape | None":
    """The chunked-scan planning fingerprint an SSM/hybrid LM config issues
    for one batch of ``tokens`` — the companion of ``model_attn_shape`` for
    the ``SCAN_ANCHOR`` row's scan schedule.  None for families with no
    recurrent mixer (pure attention)."""
    fam = getattr(cfg, "family", "attn")
    if fam == "hybrid":
        return ScanShape(
            batch=1,
            seq=tokens,
            heads=cfg.ssm_heads,
            key_dim=cfg.ssm_state,
            val_dim=cfg.ssm_head_dim,
            post_update=True,
        )
    if fam == "ssm":
        return ScanShape(
            batch=1,
            seq=tokens,
            heads=cfg.rwkv_heads,
            key_dim=cfg.rwkv_head_size,
            val_dim=cfg.rwkv_head_size,
            post_update=False,
        )
    return None


def model_epilogues(cfg) -> dict[str, EpilogueSig]:
    """Per-layer epilogue signatures matching what ``models.layers`` fuses
    into each projection's kernel — keys mirror ``model_gemms``.  Passed as
    ``autotune_plan(..., epilogue=...)`` so forward candidates are timed as
    the ops the model actually issues (bias on q/k/v when ``qkv_bias``,
    activation on mlp.w1, residual folded into attn.wo / mlp.w2)."""
    qkv = EpilogueSig(bias=cfg.qkv_bias)
    sigs = {
        "attn.wq": qkv,
        "attn.wk": qkv,
        "attn.wv": qkv,
        "attn.wo": EpilogueSig(residual=True),
        "lm_head": EpilogueSig(),
    }
    if cfg.d_ff:
        act = "silu" if cfg.activation == "silu" else "gelu"
        sigs["mlp.w1"] = EpilogueSig(activation=act)
        sigs["mlp.w2"] = EpilogueSig(residual=True)
        if cfg.activation in ("silu", "gelu"):
            sigs["mlp.w3"] = EpilogueSig()
    return sigs


def static_vs_flex_traffic(
    gemms: list[GemmShape], bm: int = 512, bk: int = 512, bn: int = 512
) -> dict[str, int]:
    """Total HBM bytes for each static dataflow vs. the flex (per-layer) plan.

    The kernel-level analogue of the paper's Table I: same exhaustive offline
    search, cost = HBM traffic instead of cycles.
    """
    totals = {df.name: 0 for df in ALL_DATAFLOWS}
    flex = 0
    for g in gemms:
        per = {df: hbm_traffic_bytes(g, df, bm, bk, bn, in_bytes=2).hbm_bytes
               for df in ALL_DATAFLOWS}
        for df, v in per.items():
            totals[df.name] += v
        flex += min(per.values())
    totals["FLEX"] = flex
    return totals
