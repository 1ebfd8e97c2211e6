"""Pallas TPU flash attention: a flex kernel family with plannable schedules.

PR 3 landed a single hard-coded online-softmax kernel (q-stationary, fixed
128x128 blocks).  This module generalizes it into the same shape the GEMM
side already has — a *family* of kernels whose schedule knobs the CMU picks
per shape and persists in the plan cache:

* ``(bq, bk)`` block sizes — tunable, not pinned to 128.
* Sweep order (``ATTN_SWEEPS``):
    - ``"q"``  (q-stationary):  grid ``(BH, nq, nkv)``.  Each q tile stays
      VMEM-resident with its f32 accumulator strip while K/V stream past.
      HBM reads K/V once *per q tile*.
    - ``"kv"`` (kv-stationary): grid ``(BH, nkv, nq)``.  Each K/V tile stays
      VMEM-resident while every q tile streams past; the accumulator /
      running-max / running-sum state for *all* rows lives in a VMEM scratch
      slab, and the output flushes once at the last kv step.  HBM reads K/V
      exactly once — the right trade for long-context prefill with GQA,
      where one resident KV head amortizes over ``group`` q heads' rows.
* A decode-shaped skinny-q variant (``paged_attention``) that reads K/V
  *in place* from the paged block pools via a scalar-prefetched block
  table — replacing the pure-jnp ``pool[table]`` gather that materialized
  a dense per-step K/V copy.
* A fused mask/softmax-scale epilogue (``_mask_scale``): scale, causal mask
  and kv-length (ragged pad) mask are applied to the score tile in VMEM,
  between the QK^T MXU op and the online-softmax update — no masked score
  tile ever round-trips to HBM.

Bitwise contract: for a fixed ``(bq, bk)`` the two sweep orders execute the
*identical* per-(i, j) update sequence for every q tile (the kv index j
ascends in both; only the interleaving across q tiles differs, and tiles
are independent), so ``sweep="q"`` and ``sweep="kv"`` agree bit-for-bit.
The property sweep in ``tests/test_flex_attention.py`` pins this.

Masking contract: prefill kernels mask additively (``-1e30``), which is
exact-zero after the softmax because every row always sees at least one
live key in its *first* kv block.  The decode kernel cannot assume that —
a sliding window can fully mask a leading block — so it zeroes masked
probabilities *multiplicatively* (see ``_paged_decode_kernel``).

Validated on CPU with interpret=True against ``ref.attention_ref``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flex_matmul import _VMEM, compiler_params

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128

#: Prefill sweep orders the CMU chooses between.
ATTN_SWEEPS = ("q", "kv")

#: Decode-attention kinds the CMU chooses between per batch bucket.
ATTN_DECODE_KINDS = ("paged", "gather")

_NEG_INF = -1e30


def _round8(d: int) -> int:
    """Round up to the fp32 sublane quantum (and at least one sublane)."""
    return max(-(-d // 8) * 8, 8)


def _mask_scale(s, i, j, bq, bk, *, scale, causal, seq, kv_len):
    """The fused mask/softmax-scale epilogue, applied to a score tile in VMEM.

    ``seq`` is the per-group logical sequence length when GQA groups are
    folded into the row axis (row r is query position ``r % seq``); None
    means rows are positions directly.  ``kv_len`` masks ragged kv padding
    (keys at ``kpos >= kv_len`` are pad).
    """
    s = s * scale
    if causal or kv_len is not None:
        qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        live = jnp.full((bq, bk), True)
        if causal:
            if seq is not None:
                qpos = jax.lax.rem(qpos, seq)
            live = live & (kpos <= qpos)
        if kv_len is not None:
            live = live & (kpos < kv_len)
        s = jnp.where(live, s, _NEG_INF)
    return s


def _online_update(s, v, m_prev, l_prev, acc_prev):
    """One flash online-softmax step.  Shared verbatim by both sweep orders
    so their per-tile arithmetic is literally the same op sequence (the
    bitwise q-vs-kv agreement contract)."""
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc_prev * corr + jnp.dot(p, v, preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _q_stationary_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                         *, scale, causal, bq, bk, seq, kv_len):
    """Grid (BH, nq, nkv): q tile resident, K/V stream (kv innermost)."""
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)          # (bq, hd)
    k = k_ref[0].astype(jnp.float32)          # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
    s = _mask_scale(s, i, j, bq, bk, scale=scale, causal=causal,
                    seq=seq, kv_len=kv_len)
    m_new, l_new, acc_new = _online_update(
        s, v, m_ref[...], l_ref[...], acc_ref[...])
    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


def _kv_stationary_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                          *, scale, causal, bq, bk, seq, kv_len, nkv):
    """Grid (BH, nkv, nq): K/V tile resident, q streams (q innermost).

    The softmax state for *all* rows lives in one VMEM slab, strip-sliced
    per q tile with ``pl.ds``; the output block is the whole row slab,
    indexed only by the batch axis, so it flushes to HBM exactly once (at
    the final kv step) — no partially-normalized tile ever leaves VMEM.
    """
    j, i = pl.program_id(1), pl.program_id(2)
    rows = pl.ds(i * bq, bq)

    @pl.when(j == 0)
    def _init():
        acc_ref[rows, :] = jnp.zeros((bq, acc_ref.shape[-1]), jnp.float32)
        m_ref[rows, :] = jnp.full((bq, 1), _NEG_INF, jnp.float32)
        l_ref[rows, :] = jnp.zeros((bq, 1), jnp.float32)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
    s = _mask_scale(s, i, j, bq, bk, scale=scale, causal=causal,
                    seq=seq, kv_len=kv_len)
    m_new, l_new, acc_new = _online_update(
        s, v, m_ref[rows, :], l_ref[rows, :], acc_ref[rows, :])
    m_ref[rows, :] = m_new
    l_ref[rows, :] = l_new
    acc_ref[rows, :] = acc_new

    @pl.when(j == nkv - 1)
    def _flush():
        o_ref[0, rows, :] = (acc_new / jnp.maximum(l_new, 1e-30)).astype(
            o_ref.dtype)


def flex_attention(q, k, v, *, sweep: str = "q", causal: bool = True,
                   scale: float | None = None,
                   block_q: int = DEFAULT_BLOCK_Q,
                   block_k: int = DEFAULT_BLOCK_K,
                   seq: int | None = None, kv_len: int | None = None,
                   interpret: bool = False):
    """Schedule-parameterized flash attention on ``(BH, rows, hd)`` operands.

    The low-level family entry: ``sweep`` and ``(block_q, block_k)`` are
    the CMU's schedule knobs.  Row and kv lengths must divide their blocks
    (``mha_flash`` handles folding/padding); ``seq``/``kv_len`` feed the
    fused mask epilogue (see ``_mask_scale``).
    """
    if sweep not in ATTN_SWEEPS:
        raise ValueError(f"sweep must be one of {ATTN_SWEEPS}, got {sweep!r}")
    BH, Sq, hd = q.shape
    Skv = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    if Sq % bq or Skv % bk:
        raise ValueError(f"seq lens ({Sq},{Skv}) must divide blocks ({bq},{bk})")
    nq, nkv = Sq // bq, Skv // bk
    knobs = dict(scale=scale, causal=causal, bq=bq, bk=bk, seq=seq,
                 kv_len=kv_len)
    if sweep == "q":
        grid = (BH, nq, nkv)
        kernel = functools.partial(_q_stationary_kernel, **knobs)
        in_specs = [
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ]
        out_spec = pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0))
        scratch = [_VMEM((bq, hd), jnp.float32),
                   _VMEM((bq, 1), jnp.float32),
                   _VMEM((bq, 1), jnp.float32)]
        semantics = ("parallel", "parallel", "arbitrary")
    else:
        grid = (BH, nkv, nq)
        kernel = functools.partial(_kv_stationary_kernel, **knobs, nkv=nkv)
        in_specs = [
            pl.BlockSpec((1, bq, hd), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, j, i: (b, j, 0)),
        ]
        # One whole-rows output block per batch index: never revisited, so
        # it flushes once (at j == nkv-1) instead of per (i, j) visit.
        out_spec = pl.BlockSpec((1, Sq, hd), lambda b, j, i: (b, 0, 0))
        scratch = [_VMEM((Sq, hd), jnp.float32),
                   _VMEM((Sq, 1), jnp.float32),
                   _VMEM((Sq, 1), jnp.float32)]
        semantics = ("parallel", "arbitrary", "arbitrary")
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=scratch,
        compiler_params=compiler_params(*semantics),
        interpret=interpret,
    )(q, k, v)


def flash_attention(
    q: jax.Array,   # (BH, Sq, hd)
    k: jax.Array,   # (BH, Skv, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """Back-compat entry: the q-stationary member of the family."""
    return flex_attention(q, k, v, sweep="q", causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret)


def mha_flash(
    q: jax.Array,   # (B, S, H, hd)
    k: jax.Array,   # (B, Skv, Hkv, hd) — GQA folded, never repeated
    v: jax.Array,
    *,
    causal: bool = True,
    interpret: bool = False,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    sweep: str = "q",
) -> jax.Array:
    """Multi-head wrapper over ``flex_attention``.

    GQA contract: no repeated K/V is ever materialized.  The group axis is
    folded into the q rows of each (batch, kv-head) kernel instance —
    ``rows = group * S``, row ``r`` is query position ``r % S`` of group
    ``r // S`` — so one resident K/V tile serves every query head sharing
    it.  Ragged lengths are handled here: rows pad up to a ``bq`` multiple
    (garbage rows sliced off after), kv pads up to a ``bk`` multiple
    (masked exactly via ``kv_len``).  Both sweeps share this wrapper, so
    the padded geometry — and therefore the bits — match across sweeps.
    """
    B, S, H, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    qf = (q.reshape(B, S, Hkv, g, hd).transpose(0, 2, 3, 1, 4)
           .reshape(B * Hkv, g * S, hd))
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, hd)
    rows = g * S
    bq = min(block_q, _round8(rows))
    bk = min(block_k, _round8(Skv))
    rows_p = -(-rows // bq) * bq
    kv_p = -(-Skv // bk) * bk
    if rows_p != rows:
        qf = jnp.pad(qf, ((0, 0), (0, rows_p - rows), (0, 0)))
    kv_len = None
    if kv_p != Skv:
        kf = jnp.pad(kf, ((0, 0), (0, kv_p - Skv), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, kv_p - Skv), (0, 0)))
        kv_len = Skv
    o = flex_attention(qf, kf, vf, sweep=sweep, causal=causal,
                       block_q=bq, block_k=bk,
                       seq=S if g > 1 else None, kv_len=kv_len,
                       interpret=interpret)
    o = o[:, :rows]
    return (o.reshape(B, Hkv, g, S, hd).transpose(0, 3, 1, 2, 4)
             .reshape(B, S, H, hd))


def heads_to_rows(q, hkv: int):
    """Queries ``(B, H, hd)`` as block-diagonal rows ``(B, H, Hkv·hd)``:
    row ``j·Hkv + h`` holds query ``j`` of KV head ``h`` in columns
    ``h·hd … h·hd+hd−1`` and zeros elsewhere.  One product of these rows
    with a K row (a position's heads side by side, as the paged pools hold
    it) scores every head, and no head is ever split out of K: on the TPU
    that split is a relayout of the whole view, padded to 128 lanes where
    hd is 64.  The rows go group-major so that ``rows_to_heads`` reads the
    product's rows ``(B, g, Hkv, ·)`` as a bitcast."""
    B, H, hd = q.shape
    own = jnp.eye(hkv, dtype=q.dtype)
    qg = q.reshape(B, hkv, H // hkv, hd).swapaxes(1, 2)  # (B, g, Hkv, hd)
    return (qg[:, :, :, None, :] * own[:, :, None]).reshape(B, H, hkv * hd)


def rows_to_heads(x, hd: int):
    """The inverse of ``heads_to_rows`` for a product with V's rows:
    ``(B, H, Hkv·hd)`` -> ``(B, H, hd)`` in head order, each query head's
    own hd columns (the other heads' columns are multiplied by exact
    zeros)."""
    B, H, C = x.shape
    hkv = C // hd
    own = jnp.repeat(jnp.eye(hkv, dtype=x.dtype), hd, axis=1)  # (Hkv, Hkv·hd)
    o = (x.reshape(B, H // hkv, hkv, C) * own).sum(2)  # (B, g, Hkv·hd)
    return o.reshape(B, H // hkv, hkv, hd).swapaxes(1, 2).reshape(B, H, hd)


def row_values_to_heads(v, hkv: int):
    """A value per row of ``heads_to_rows`` ``(B, H)``, in head order."""
    B, H = v.shape
    return v.reshape(B, H // hkv, hkv).swapaxes(1, 2).reshape(B, H)


def _paged_decode_kernel(table_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, scale, window, bs):
    """Grid (B, nb): one decode slot's query heads resident, as
    block-diagonal rows (``heads_to_rows``); K/V blocks stream straight out
    of the paged pools (the scalar-prefetched block table picks the pool
    row per grid step — no dense gather copy), ``(bs, Hkv·hd)`` as the
    pools hold them.  Each product runs over whole rows; the wrapper keeps
    each head's own columns of the output.

    Masked probabilities are zeroed *multiplicatively*: with a sliding
    window the leading blocks of a deep sequence can be fully masked,
    which leaves the running max at the ``-1e30`` sentinel — the additive
    mask alone would then contribute ``exp(-1e30 - (-1e30)) = 1`` per
    masked key, poisoning the running sum.  ``where(live, exp(...), 0)``
    is exact zero regardless of the sentinel, and bit-identical for live
    keys.
    """
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)      # (H, Hkv·hd), block-diagonal
    k = k_ref[0].astype(jnp.float32)      # (bs, Hkv·hd)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)[0]
    pos = pos_ref[b]
    live = kpos <= pos
    if window:
        live = live & (pos - kpos < window)
    live = live[None, :]
    s = jnp.where(live, s, _NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(live, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


def paged_attention(q, pool_k, pool_v, table, positions, *,
                    scale: float | None = None, window: int = 0,
                    interpret: bool = False):
    """Decode-shaped skinny-q attention reading K/V blocks in place.

    ``q``: (B, H, hd) — one new token per slot.  ``pool_k/v``:
    (num_blocks, bs, Hkv·hd) paged pools, a position's heads side by side.
    ``table``: (B, nb) int32 block table; ``positions``: (B,) int32 current
    position per slot.  Each slot computes independently, so pad slots
    (all-scratch tables, position 0) cannot perturb live rows — the
    scheduler's bucket-padding contract.  Returns (B, H, hd) in
    ``q.dtype``.
    """
    B, H, hd = q.shape
    bs, C = pool_k.shape[1], pool_k.shape[2]
    nb = table.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    kernel = functools.partial(_paged_decode_kernel, scale=scale,
                               window=window, bs=bs)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, H, C), lambda b, j, tbl, ps: (b, 0, 0)),
            pl.BlockSpec((1, bs, C), lambda b, j, tbl, ps: (tbl[b, j], 0, 0)),
            pl.BlockSpec((1, bs, C), lambda b, j, tbl, ps: (tbl[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, C), lambda b, j, tbl, ps: (b, 0, 0)),
        scratch_shapes=[
            _VMEM((H, C), jnp.float32),
            _VMEM((H, 1), jnp.float32),
            _VMEM((H, 1), jnp.float32),
        ],
    )
    rows = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, C), q.dtype),
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(table, positions, heads_to_rows(q, C // hd), pool_k, pool_v)
    return rows_to_heads(rows, hd)


def paged_attention_reference(q, pool_k, pool_v, table, positions, *,
                              scale: float | None = None, window: int = 0):
    """The pure-jnp gather baseline: densify K/V through the block table,
    single-pass global-max softmax (``_decode_core`` math).  The "gather"
    decode kind the CMU times against the paged kernel, and the oracle the
    property sweep checks it against."""
    B, H, hd = q.shape
    Hkv = pool_k.shape[2] // hd
    group = H // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    k = pool_k[table].reshape(B, -1, Hkv, hd).astype(jnp.float32)
    v = pool_v[table].reshape(B, -1, Hkv, hd).astype(jnp.float32)
    qg = q.reshape(B, Hkv, group, hd).astype(jnp.float32)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k) * scale
    kpos = jnp.arange(k.shape[1], dtype=jnp.int32)
    live = kpos[None, :] <= positions[:, None]
    if window:
        live = live & (positions[:, None] - kpos[None, :] < window)
    s = jnp.where(live[:, None, None, :], s, _NEG_INF)
    mx = jnp.max(s, axis=-1, keepdims=True)
    pr = jnp.exp(s - mx)
    num = jnp.einsum("bhgk,bkhd->bhgd", pr, v)
    den = jnp.sum(pr, axis=-1, keepdims=True)
    o = num / jnp.maximum(den, 1e-30)
    return o.reshape(B, H, hd).astype(q.dtype)
