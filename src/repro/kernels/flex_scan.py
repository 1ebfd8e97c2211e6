"""Flex chunked-scan kernels: the SSM analogue of ``flex_attention``.

Both Mamba2 (SSD) and RWKV-6 reduce to a diagonal-decay linear attention

    S_t = diag(w_t) S_{t-1} + k_t v_t^T ,   o_t = r_t^T S'_t

whose chunked form is exactly the GEMM family the CMU already schedules:
per chunk, an (L, L) intra-chunk score GEMM, an (L, M) output GEMM and an
(N, M) state-update GEMM.  This module exposes that scan as a *schedule
family* over folded ``(B*H, C, L, .)`` operands with two CMU knobs:

``chunk``
    The intra-chunk length L.  Bounded by exp-safety: every in-chunk
    exponent is within ``|LOG_DECAY_MIN| * chunk``, so candidates keep
    ``3 * chunk < 88`` (f32 exp range).

``sweep`` — where the running (N, M) f32 state lives across the chunk grid:

    "state" (state-stationary)
        The whole ``(B*H*N, M)`` state slab is a single never-moving output
        block: it stays VMEM-resident across the entire grid and is written
        to HBM exactly once at the end.  Maximum VMEM footprint, minimum
        state traffic — the schedule the 96 MiB budget prunes first as
        ``B*H*N*M`` grows.
    "out" (output-stationary)
        The state is a per-(b, h) ``(N, M)`` output block revisited
        *non-consecutively* across the outer chunk axis, so it streams
        through HBM (read-modify-write) once per chunk step — the same
        revisiting semantics the streamed WS/IS matmul kernels use for
        partial sums.  Minimum VMEM, ~2C x state HBM traffic.

Both sweeps run the identical grid ``(C, B*H)`` (chunks outer) and the
identical ``_chunk_update`` op sequence — the sweep changes *where* the
state lives, never the arithmetic — so the two schedules agree **bitwise**.

The fused epilogue covers both recurrence conventions: RWKV
(``post_update=False``: output reads the pre-update state, strict-lower
intra-chunk mask, plus the u-bonus diagonal) and Mamba2
(``post_update=True``: post-update state, inclusive mask, no bonus).

``flex_recurrent_step`` is the decode-shaped member: one fused O(1) step of
the same recurrence over ``(B*H, .)`` operands.

Validated on CPU with interpret=True against
``models.ssm.chunked_diag_linear_attn`` (tests/test_flex_ssm.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.flex_matmul import compiler_params

#: Chunk-grid sweep orders (where the running state lives).
SCAN_SWEEPS = ("state", "out")

#: Decode kinds: the fused Pallas step kernel vs the jnp recurrence.
#: (Chunk-length candidates live in ``core.dataflow.SCAN_CHUNK_CANDIDATES``,
#: next to the traffic model that prices them.)
SCAN_DECODE_KINDS = ("fused", "einsum")


def _chunk_update(rc, kc, vc, lw, u, S, *, post_update: bool):
    """One chunk of the diagonal-decay recurrence, all f32.

    rc/kc/lw: (L, N); vc: (L, M); u: (1, N) bonus row or None; S: (N, M).
    Returns (o (L, M), S_new (N, M)).

    Shared verbatim by both sweeps: the sweep decides where S lives (VMEM
    slab vs HBM-streamed block), never the op sequence, so the two
    schedules agree bitwise.  The factoring matches
    ``models.ssm.chunked_diag_linear_attn``: with cum = inclusive
    cumsum(log_w), r_fac = r*exp(cum or cum_prev) has exponents <= 0 and
    k_fac = k*exp(-cum) exponents <= |LOG_DECAY_MIN|*L — all f32-safe.
    """
    L = rc.shape[0]
    cum = jnp.cumsum(lw, axis=0)
    cum_prev = cum - lw
    r_fac = rc * jnp.exp(cum if post_update else cum_prev)
    k_fac = kc * jnp.exp(-cum)
    scores = jax.lax.dot_general(
        r_fac, k_fac, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ri = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    # strict lower triangle (j<i) for RWKV; lower incl. diagonal for Mamba2
    mask = (ci <= ri) if post_update else (ci < ri)
    scores = jnp.where(mask, scores, 0.0)
    o = jax.lax.dot_general(
        scores, vc, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if u is not None:  # RWKV u-bonus diagonal (pre-update convention)
        o = o + jnp.sum(rc * u * kc, axis=1, keepdims=True) * vc
    # inter-chunk: contribution of the carried state
    o = o + jax.lax.dot_general(
        r_fac, S, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    # state update: decay the carry across the chunk, add the k v^T tail
    decay_all = jnp.exp(cum[-1:])                 # (1, N)
    k_tail = kc * jnp.exp(cum[-1:] - cum)         # exponent <= 0
    S_new = S * decay_all.T + jax.lax.dot_general(
        k_tail, vc, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return o, S_new


def _scan_kernel(*refs, sweep: str, post_update: bool, n: int):
    if post_update:
        r_ref, k_ref, v_ref, lw_ref, o_ref, s_ref = refs
        u = None
    else:
        r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_ref = refs
        u = u_ref[...]  # (1, N) f32
    c, bh = pl.program_id(0), pl.program_id(1)
    rc = r_ref[0, 0].astype(jnp.float32)   # (L, N)
    kc = k_ref[0, 0].astype(jnp.float32)
    vc = v_ref[0, 0].astype(jnp.float32)   # (L, M)
    lw = lw_ref[0, 0]                      # (L, N) f32
    if sweep == "state":
        # whole-slab output block, never moving: this row stays VMEM-resident
        S = s_ref[pl.ds(bh * n, n), :]
    else:
        # per-(b,h) block revisited each c: streams through HBM between chunks
        S = s_ref[...]
    S = jnp.where(c == 0, jnp.zeros_like(S), S)
    o, S_new = _chunk_update(rc, kc, vc, lw, u, S, post_update=post_update)
    o_ref[0, 0] = o.astype(o_ref.dtype)
    if sweep == "state":
        s_ref[pl.ds(bh * n, n), :] = S_new
    else:
        s_ref[...] = S_new


def flex_scan(
    r: jax.Array,       # (B, T, H, N)
    k: jax.Array,       # (B, T, H, N)
    v: jax.Array,       # (B, T, H, M)
    log_w: jax.Array,   # (B, T, H, N), in [LOG_DECAY_MIN, 0]
    diag_scale: jax.Array | None = None,  # (H, N) RWKV u bonus; None -> ones
    *,
    chunk: int = 16,
    sweep: str = "state",
    post_update: bool = False,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Schedule-parameterized chunked scan.  Returns (o (B,T,H,M) in
    ``v.dtype``, final state (B,H,N,M) f32), matching
    ``models.ssm.chunked_diag_linear_attn`` with ``state0=None``.

    ``sweep`` and ``chunk`` are the CMU's schedule knobs (see module
    docstring).  T must divide ``chunk``; the model-side dispatch pads
    ragged T with zero rows, which are exact no-ops for both outputs
    (``models.ssm._pad_chunks``).
    """
    if sweep not in SCAN_SWEEPS:
        raise ValueError(f"sweep must be one of {SCAN_SWEEPS}, got {sweep!r}")
    B, T, H, N = r.shape
    M = v.shape[-1]
    if T % chunk:
        raise ValueError(f"T={T} must divide chunk={chunk}")
    C, L = T // chunk, chunk
    BH = B * H

    def fold(a, d):
        return jnp.moveaxis(a, 2, 1).reshape(BH, C, L, d)

    inputs = [fold(r, N), fold(k, N), fold(v, M),
              fold(log_w.astype(jnp.float32), N)]
    in_specs = [
        pl.BlockSpec((1, 1, L, N), lambda c, bh: (bh, c, 0, 0)),
        pl.BlockSpec((1, 1, L, N), lambda c, bh: (bh, c, 0, 0)),
        pl.BlockSpec((1, 1, L, M), lambda c, bh: (bh, c, 0, 0)),
        pl.BlockSpec((1, 1, L, N), lambda c, bh: (bh, c, 0, 0)),
    ]
    if not post_update:
        ds = (jnp.ones((H, N), jnp.float32) if diag_scale is None
              else diag_scale.astype(jnp.float32))
        inputs.append(jnp.broadcast_to(ds[None], (B, H, N)).reshape(BH, N))
        in_specs.append(pl.BlockSpec((1, N), lambda c, bh: (bh, 0)))
    if sweep == "state":
        s_spec = pl.BlockSpec((BH * N, M), lambda c, bh: (0, 0))
    else:
        s_spec = pl.BlockSpec((N, M), lambda c, bh: (bh, 0))
    if interpret is None:
        from repro.kernels import ops

        interpret = ops.default_interpret()
    if sweep == "out" and not interpret:
        raise ValueError(
            "the 'out' sweep revisits its state blocks across chunks, which "
            "Mosaic never reads back: on the chip use sweep='state'")
    o, S = pl.pallas_call(
        functools.partial(_scan_kernel, sweep=sweep,
                          post_update=post_update, n=N),
        grid=(C, BH),  # chunks OUTER: every (b,h) advances one chunk per row
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, 1, L, M), lambda c, bh: (bh, c, 0, 0)),
                   s_spec],
        out_shape=[jax.ShapeDtypeStruct((BH, C, L, M), v.dtype),
                   jax.ShapeDtypeStruct((BH * N, M), jnp.float32)],
        compiler_params=compiler_params("arbitrary", "arbitrary"),
        interpret=interpret,
    )(*inputs)
    o = jnp.moveaxis(o.reshape(B, H, T, M), 1, 2)
    return o, S.reshape(B, H, N, M)


def _step_kernel(*refs, post_update: bool):
    if post_update:
        r_ref, k_ref, v_ref, lw_ref, s0_ref, o_ref, s_ref = refs
        u = None
    else:
        r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, o_ref, s_ref = refs
        u = u_ref[...]                     # (BH, N) f32
    r = r_ref[...].astype(jnp.float32)     # (BH, N)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)     # (BH, M)
    lw = lw_ref[...]                       # (BH, N) f32
    S = s0_ref[...]                        # (BH, N, M) f32
    S_new = S * jnp.exp(lw)[:, :, None] + k[:, :, None] * v[:, None, :]
    if post_update:  # Mamba2: output reads the post-update state
        o = jax.lax.dot_general(
            r, S_new, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
    else:  # RWKV: pre-update state + u-bonus diagonal
        o = jax.lax.dot_general(
            r, S, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        o = o + jnp.sum(r * u * k, axis=1, keepdims=True) * v
    o_ref[...] = o.astype(o_ref.dtype)
    s_ref[...] = S_new


def flex_recurrent_step(
    r: jax.Array,       # (B, H, N)
    k: jax.Array,
    v: jax.Array,       # (B, H, M)
    log_w: jax.Array,   # (B, H, N)
    S: jax.Array,       # (B, H, N, M) f32
    diag_scale: jax.Array | None = None,
    *,
    post_update: bool = False,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One fused decode step of the recurrence — the Pallas counterpart of
    ``models.ssm.recurrent_step`` (same signature semantics).  The whole
    bucketed batch runs as a single fused kernel: state in, state out, one
    HBM round trip, no jnp intermediate for the k v^T outer product."""
    B, H, N = r.shape
    M = v.shape[-1]
    BH = B * H
    inputs = [r.reshape(BH, N), k.reshape(BH, N), v.reshape(BH, M),
              log_w.astype(jnp.float32).reshape(BH, N)]
    if not post_update:
        ds = (jnp.ones((H, N), jnp.float32) if diag_scale is None
              else diag_scale.astype(jnp.float32))
        inputs.append(jnp.broadcast_to(ds[None], (B, H, N)).reshape(BH, N))
    inputs.append(S.reshape(BH, N, M).astype(jnp.float32))
    if interpret is None:
        from repro.kernels import ops

        interpret = ops.default_interpret()
    o, S_new = pl.pallas_call(
        functools.partial(_step_kernel, post_update=post_update),
        out_shape=[jax.ShapeDtypeStruct((BH, M), v.dtype),
                   jax.ShapeDtypeStruct((BH, N, M), jnp.float32)],
        compiler_params=compiler_params(),
        interpret=interpret,
    )(*inputs)
    return o.reshape(B, H, M), S_new.reshape(B, H, N, M)
