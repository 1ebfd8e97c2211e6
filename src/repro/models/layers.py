"""Transformer layer primitives shared by all ten architectures.

Everything is functional: ``params`` are nested dicts of arrays, layers are
pure functions of (params, x).  Activation sharding uses logical axes
(`sharding.constrain`), a no-op outside a mesh context.  Dense projections
route through ``linear`` which dispatches to the fused Pallas flex kernels
(config.use_pallas: bias/activation/residual fused into the kernel flush,
dataflow + block per the active CMU plan) or plain XLA einsum (dry-run
path, where XLA must see a fusible dot for cost_analysis).
"""

from __future__ import annotations

import contextlib
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.sharding import constrain

Params = dict[str, Any]

_XLA_ACT = {"relu": jax.nn.relu, "gelu": jax.nn.gelu, "silu": jax.nn.silu}


def linear(
    cfg: ModelConfig,
    x: jax.Array,
    w: jax.Array,
    b: jax.Array | None = None,
    *,
    activation: str | None = None,
    residual: jax.Array | None = None,
    name: str = "",
) -> jax.Array:
    """``act(x @ w + b) + residual`` for (..., K) @ (K, N).

    With ``cfg.use_pallas`` this is one fused flex-kernel launch: the CMU
    plan (``core.plan_cache.active_plan``) supplies (dataflow, block) for
    ``name`` — including the per-layer backward sub-plans, so under
    ``jax.grad`` the cotangent GEMMs also run as flex kernels under their
    own dataflows.  Unplanned layers fall back to the trace-time roofline
    argmin.  Otherwise plain XLA ops (einsum + separate epilogue), the
    dry-run path.

    When the plan row (or the decode-bucket sub-plan that overrides it)
    carries a quantized ``qdtype`` verdict ("int8"/"fp8"), the dispatch
    quantizes the weight per output channel and the kernel fuses the
    dequant into its flush epilogue; "bf16" and None run full precision,
    and the mesh-native sharded path never quantizes.

    When a rules context is active (``sharding.use_rules``) and the GEMM
    divides the mesh, the Pallas path goes **mesh-native**: the layer runs
    as a shard_map-composed collective schedule around the local flex
    kernels (``kernels.mesh_ops.flex_linear_sharded``), with the mesh-level
    dataflow and local per-shard geometry from the plan's ``mesh``
    sub-plan (or the trace-time analytical argmin).  Layers that don't
    divide the mesh fall back cleanly to the single-device kernel path —
    the same contract as the attention shard_map path.

    The layer runs under ``jax.named_scope(name)``; inside it the kernel
    runs under its dispatched dataflow's scope (``is``/``os``/``ws``, set
    by ``kernels.ops``) and the XLA path under ``xla``, so that a device
    trace gives the time of each projection and of each path.
    """
    with jax.named_scope(name) if name else contextlib.nullcontext():
        return _linear(cfg, x, w, b, activation=activation,
                       residual=residual, name=name)


def _linear(cfg, x, w, b, *, activation, residual, name):
    w = w.astype(x.dtype)
    if cfg.use_pallas:
        from repro.core.dataflow import GemmShape, best_kernel_dataflow
        from repro.core.plan_cache import active_plan
        from repro.kernels.flex_matmul import DEFAULT_BLOCK
        from repro.kernels.ops import default_interpret, flex_linear

        lead = x.shape[:-1]
        K, N = w.shape
        x2 = x.reshape(-1, K)
        r2 = None if residual is None else residual.reshape(-1, N)
        plan = active_plan()
        lp = plan.get(name) if (plan is not None and name) else None

        from repro.models.sharding import active_mesh, spec_for, tensor_axis

        mesh = active_mesh()
        axis = tensor_axis() if mesh is not None else None
        if axis is not None:
            from repro.core.cmu import mesh_shardable
            from repro.kernels.mesh_ops import flex_linear_sharded
            from repro.launch.mesh import dp_size as mesh_dp_size

            dp_axes = spec_for("act_batch")[0] or ()
            dp_axes = ((dp_axes,) if isinstance(dp_axes, str)
                       else tuple(dp_axes))
            tp = int(mesh.shape[axis])
            dp = mesh_dp_size(mesh, dp_axes)
            gemm = GemmShape(x2.shape[0], K, N, name=name)
            if mesh_shardable(gemm, tp, dp):
                out = flex_linear_sharded(
                    x2, w, None if b is None else b.astype(x.dtype),
                    mesh=mesh, axis=axis, dp_axes=dp_axes,
                    activation=activation, residual=r2,
                    plan=lp.mesh if lp is not None else None,
                    interpret=default_interpret(), out_dtype=x.dtype,
                )
                return out.reshape(*lead, N)

        bwd_dx = bwd_dw = None
        strip = 1
        qdtype = None
        if lp is not None:
            df, blk, strip = lp.dataflow, lp.block or DEFAULT_BLOCK, lp.strip
            qdtype = lp.qdtype
            # decode-bucket dispatch: a skinny (decode-geometry) call whose
            # row count fits a tuned batch-size bucket runs that bucket's
            # plan — the serving scheduler quantizes its live batch to the
            # same buckets, so every decode step hits a pre-tuned geometry
            sub = lp.decode_plan(x2.shape[0]) if lp.decode else None
            if sub is not None:
                df, blk, strip = sub.dataflow, sub.block or DEFAULT_BLOCK, sub.strip
                qdtype = sub.qdtype
            if lp.bwd_dx is not None:
                bwd_dx = (lp.bwd_dx.dataflow, lp.bwd_dx.block, lp.bwd_dx.trans,
                          lp.bwd_dx.strip)
            if lp.bwd_dw is not None:
                bwd_dw = (lp.bwd_dw.dataflow, lp.bwd_dw.block, lp.bwd_dw.trans,
                          lp.bwd_dw.strip)
        else:
            df, _ = best_kernel_dataflow(GemmShape(x2.shape[0], K, N, name=name))
            blk = DEFAULT_BLOCK
        # only a quantized verdict dispatches quantized — None (v1–v8 plan)
        # and "bf16" (quant searched and rejected) both run full precision
        if qdtype not in ("int8", "fp8"):
            qdtype = None
        out = flex_linear(
            x2, w, None if b is None else b.astype(x.dtype),
            activation=activation, residual=r2, dataflow=df, block=blk,
            interpret=default_interpret(), out_dtype=x.dtype,
            bwd_dx=bwd_dx, bwd_dw=bwd_dw, strip=strip, qdtype=qdtype,
        )
        return out.reshape(*lead, N)
    with jax.named_scope("xla"):
        y = jnp.einsum("...d,df->...f", x, w)
        if b is not None:
            y = y + b.astype(y.dtype)
        if activation is not None:
            y = _XLA_ACT[activation](y)
        if residual is not None:
            y = y + residual
    return y


# ---------------------------------------------------------------------------
# initialisation helpers
# ---------------------------------------------------------------------------


def _init(key, shape, scale=None, dtype=jnp.float32):
    scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
    return jax.random.normal(key, shape, dtype) * scale


def split_keys(key, n):
    return list(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * scale + bias).astype(dt)


def norm(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    if cfg.norm == "layernorm":
        return layernorm(x, p["scale"], p["bias"], cfg.norm_eps)
    return rmsnorm(x, p["scale"], cfg.norm_eps)


def init_norm(cfg: ModelConfig, key) -> Params:
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((cfg.d_model,)), "bias": jnp.zeros((cfg.d_model,))}
    return {"scale": jnp.zeros((cfg.d_model,))}


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) or (S,)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos(seq: int, dim: int) -> jax.Array:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    inv = jnp.exp(-math.log(10000.0) * jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = pos * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def init_attention(cfg: ModelConfig, key) -> Params:
    ks = split_keys(key, 4)
    D = cfg.d_model
    p: Params = {
        "wq": _init(ks[0], (D, cfg.q_dim)),
        "wk": _init(ks[1], (D, cfg.kv_dim)),
        "wv": _init(ks[2], (D, cfg.kv_dim)),
        "wo": _init(ks[3], (cfg.q_dim, D)),
    }
    if cfg.qkv_bias:
        p |= {
            "bq": jnp.zeros((cfg.q_dim,)),
            "bk": jnp.zeros((cfg.kv_dim,)),
            "bv": jnp.zeros((cfg.kv_dim,)),
        }
    if cfg.qk_norm:
        p |= {"q_norm": jnp.zeros((cfg.head_dim,)), "k_norm": jnp.zeros((cfg.head_dim,))}
    return p


def _project_qkv(cfg: ModelConfig, p: Params, x: jax.Array, xkv: jax.Array | None = None):
    """Returns q (B,S,H,hd), k/v (B,Skv,Hkv,hd)."""
    B, S, _ = x.shape
    xkv = x if xkv is None else xkv
    Skv = xkv.shape[1]
    q = linear(cfg, x, p["wq"], p.get("bq"), name="attn.wq")
    k = linear(cfg, xkv, p["wk"], p.get("bk"), name="attn.wk")
    v = linear(cfg, xkv, p["wv"], p.get("bv"), name="attn.wv")
    # Attention is context-parallel (seq-sharded q under shard_map), so the
    # flat projections stay SEQ-sharded and heads are never split — this is
    # head-count agnostic (56 or 8 heads on a 16-way axis both just work) and
    # avoids the reshape-misalignment full-remats GSPMD produces otherwise.
    q = constrain(q, "act_batch", "act_seq", None)
    k = constrain(k, "act_batch", "act_seq", None)
    v = constrain(v, "act_batch", "act_seq", None)
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, Skv, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, Skv, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def _sdpa(q, k, v, mask, scale):
    """q (B,Sq,H,hd), k/v (B,Sk,Hkv,hd) GQA; mask (B|1, 1, Sq, Sk) bool.

    Score tensors are the attention memory hot-spot; they're sharded over the
    query dim ('act_seq' -> tensor axis) because head counts (8 kv / 7 group)
    rarely divide a 16-way axis while query chunks always do.
    """
    B, Sq, H, hd = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Sq, Hkv, g, hd)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32), k.astype(jnp.float32))
    scores = constrain(scores, "act_batch", None, None, "act_seq", None)
    scores = scores * scale
    scores = jnp.where(mask[:, :, None] if mask.ndim == 4 else mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = constrain(probs, "act_batch", None, None, "act_seq", None)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def _sdpa_local(q, k, v, mask, scale):
    """GQA attention on LOCAL (unsharded) arrays — the shard_map inner body."""
    B, Sq, H, hd = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Sq, Hkv, g, hd)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32), k.astype(jnp.float32))
    scores = scores * scale
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def _attention_core(
    cfg: ModelConfig,
    q: jax.Array,          # (B, Sq_local, H, hd)
    k: jax.Array,          # (B, Skv, Hkv, hd) — full kv
    v: jax.Array,
    *,
    q_offset,              # global position of q[0] (int or traced scalar)
    causal: bool,
    window: int,
    prefix_len: int,
    scale: float,
) -> jax.Array:
    """Online-softmax (flash) local attention — OUTPUT-STATIONARY in the
    paper's vocabulary: the (cq, hd) output tile and its running max/sum stay
    resident while KV tiles stream past; only (cq x ckv) score tiles ever
    materialise.  Runs identically under shard_map (q seq-sharded,
    q_offset = shard index * shard length) and standalone.  Windowed layers
    stream only a (window + cq)-wide KV slice — sub-quadratic for gemma3's
    local layers.  With cfg.attn_unroll the loops are python-unrolled with
    STATIC per-q-chunk KV bounds (exact HLO costs, no masked-tile waste)."""
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    Hkv = k.shape[2]
    g = H // Hkv
    # KV tiles of attn_chunk whatever the length: a ragged sequence is
    # padded up to whole tiles (pad keys masked, pad query rows dropped), so
    # a position's output never depends on how far its sequence was padded
    # — the online softmax sums in tile order, and a prompt served at its
    # exact length must equal the same prompt padded to a length bucket.
    cq = min(cfg.attn_chunk, Sq)
    ckv = cfg.attn_chunk
    Sq_pad, Skv_pad = -(-Sq // cq) * cq, -(-Skv // ckv) * ckv
    if Sq_pad != Sq:
        q = jnp.pad(q, ((0, 0), (0, Sq_pad - Sq), (0, 0), (0, 0)))
    if Skv_pad != Skv:
        k = jnp.pad(k, ((0, 0), (0, Skv_pad - Skv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Skv_pad - Skv), (0, 0), (0, 0)))
    nq, nkv = Sq_pad // cq, Skv_pad // ckv
    Sq, Sq_out, Skv_real, Skv = Sq_pad, Sq, Skv, Skv_pad
    kv_slice = min(Skv, window + cq) if (window and causal) else Skv

    def kv_tile(carry, q_c, qpos, kv0):
        """One KV tile starting at kv0: update (acc, m_run, l_run) online."""
        acc, m_run, l_run, _ = carry
        k_c = jax.lax.dynamic_slice_in_dim(k, kv0, ckv, axis=1)
        v_c = jax.lax.dynamic_slice_in_dim(v, kv0, ckv, axis=1)
        kpos = kv0 + jnp.arange(ckv)
        qg = q_c.reshape(B, cq, Hkv, g, hd)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32), k_c.astype(jnp.float32))
        s = s * scale
        m = jnp.broadcast_to(kpos[None, :] < Skv_real, (cq, ckv))
        if causal:
            m = m & (kpos[None, :] <= qpos[:, None])
            if window:
                m = m & (qpos[:, None] - kpos[None, :] < window)
            if prefix_len:
                m = m | (kpos[None, :] < prefix_len)
        s = jnp.where(m[None, None, None], s, -1e30)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_run - m_new)
        l_run = l_run * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bhgqd", p, v_c.astype(jnp.float32))
        acc = acc * corr[..., None] + pv
        return (acc, m_new, l_run, 0), None

    def q_chunk(c):
        """Full online pass of one q chunk over its needed KV range."""
        q_c = jax.lax.dynamic_slice_in_dim(q, c * cq, cq, axis=1)
        qpos = q_offset + c * cq + jnp.arange(cq)
        # tie the carry init to q so its manual-axes "varying" status matches
        # the loop body's outputs under shard_map (folded away by XLA)
        zero = (q_c.astype(jnp.float32) * 0.0).sum()
        acc = jnp.zeros((B, Hkv, g, cq, hd), jnp.float32) + zero
        m_run = jnp.full((B, Hkv, g, cq), -1e30, jnp.float32) + zero
        l_run = jnp.zeros((B, Hkv, g, cq), jnp.float32) + zero
        if window and causal:
            start = jnp.clip(qpos[-1] + 1 - kv_slice, 0, Skv - kv_slice)
            # windowed: a fixed-width slice, tiled in one pass
            n_t = max(kv_slice // ckv, 1)
            ct = kv_slice // n_t
            carry = (acc, m_run, l_run, 0)
            for t in range(n_t):
                k0 = start + t * ct
                kc = jax.lax.dynamic_slice_in_dim(k, k0, ct, axis=1)
                vc = jax.lax.dynamic_slice_in_dim(v, k0, ct, axis=1)
                kpos = k0 + jnp.arange(ct)
                qg = q_c.reshape(B, cq, Hkv, g, hd)
                s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32), kc.astype(jnp.float32)) * scale
                m = ((kpos[None, :] <= qpos[:, None])
                     & (qpos[:, None] - kpos[None, :] < window)
                     & (kpos[None, :] < Skv_real))
                if prefix_len:
                    m = m | (kpos[None, :] < prefix_len)
                s = jnp.where(m[None, None, None], s, -1e30)
                m_new = jnp.maximum(carry[1], jnp.max(s, axis=-1))
                p = jnp.exp(s - m_new[..., None])
                corr = jnp.exp(carry[1] - m_new)
                l_new = carry[2] * corr + jnp.sum(p, axis=-1)
                pv = jnp.einsum("bhgqk,bkhd->bhgqd", p, vc.astype(jnp.float32))
                acc_new = carry[0] * corr[..., None] + pv
                carry = (acc_new, m_new, l_new, 0)
            acc, m_run, l_run, _ = carry
        elif cfg.attn_unroll:
            # probe path: python-unrolled; static causal bound when the shard
            # offset is static, else conservatively all tiles (costs are then
            # an upper bound — documented in EXPERIMENTS §Roofline)
            carry = (acc, m_run, l_run, 0)
            for t in range(nkv):
                if causal and isinstance(q_offset, int) and t * ckv > q_offset + (c + 1) * cq - 1:
                    break
                carry, _ = kv_tile(carry, q_c, qpos, t * ckv)
            acc, m_run, l_run, _ = carry
        else:
            # differentiable path: scan all KV tiles (masked tiles waste ~2x
            # attention FLOPs for causal runs — the Pallas flash kernel with
            # a bounded grid is the production fix, kernels/flash_attention)
            def body(carry, t):
                carry, _ = kv_tile(carry, q_c, qpos, t * ckv)
                return carry, None

            (acc, m_run, l_run, _), _ = jax.lax.scan(
                body, (acc, m_run, l_run, 0), jnp.arange(nkv)
            )
        o = acc / jnp.maximum(l_run, 1e-30)[..., None]
        # (B,Hkv,g,cq,hd) -> (B,cq,H,hd)
        return jnp.moveaxis(o, 3, 1).reshape(B, cq, H, hd).astype(q.dtype)

    q_chunk_ck = jax.checkpoint(q_chunk, static_argnums=())
    if nq == 1:
        return q_chunk(0)[:, :Sq_out]
    if cfg.attn_unroll:
        return jnp.concatenate([q_chunk(c) for c in range(nq)], axis=1)[:, :Sq_out]
    _, os = jax.lax.scan(lambda _, c: (None, q_chunk_ck(c)), None, jnp.arange(nq))
    return jnp.moveaxis(os, 0, 1).reshape(B, Sq, H, hd)[:, :Sq_out]


def _attn_schedule() -> tuple[str, tuple[int, int]]:
    """The planned flash-attention schedule (sweep, (bq, bk)) from the
    active CMU plan's anchor row, or the default q-stationary 128x128 when
    no plan (or a pre-v7 plan) is active."""
    from repro.core.plan_cache import active_plan

    plan = active_plan()
    ap = plan.attention_plan() if plan is not None else None
    if ap is None or len(ap.block) < 2:
        return "q", (128, 128)
    return ap.sweep, (ap.block[0], ap.block[1])


def _attn_decode_kind(batch: int) -> str:
    """The planned decode-attention kind for a ``batch``-slot dispatch:
    the bucketed sub-plan's pick, else "paged" (turning ``attn_pallas`` on
    without a plan runs the Pallas kernel everywhere)."""
    from repro.core.plan_cache import active_plan

    plan = active_plan()
    ap = plan.attention_plan() if plan is not None else None
    sub = ap.decode_plan(batch) if ap is not None else None
    return sub.sweep if sub is not None else "paged"


def attention_full(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,
    *,
    window: int = 0,
    prefix_len: int = 0,
    causal: bool = True,
    xkv: jax.Array | None = None,
    use_rope: bool = True,
    positions: jax.Array | None = None,
    residual: jax.Array | None = None,
) -> jax.Array:
    """Full-sequence attention (train / prefill): context-parallel shard_map.

    q is sequence-sharded over the tensor axis; K/V are gathered per shard
    (they're GQA-small).  Inside each shard a chunked flash-style scan bounds
    score memory; windowed layers touch only a (window + chunk) KV slice, so
    gemma3's local layers stay sub-quadratic in the HLO.  Falls back to the
    single-device path when no mesh is active or shapes don't divide.
    """
    B, S, D = x.shape
    q, k, v = _project_qkv(cfg, p, x, xkv)
    Skv = k.shape[1]
    if positions is None:
        positions = jnp.arange(S)
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, jnp.arange(Skv), cfg.rope_theta)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    core = dict(causal=causal, window=window, prefix_len=prefix_len, scale=scale)

    with jax.named_scope("attn.core"):
        o = _attention_full_core(cfg, q, k, v, core)
    o = constrain(o, "act_batch", "act_seq", None, None)
    return linear(cfg, o.reshape(B, S, cfg.q_dim), p["wo"],
                  residual=residual, name="attn.wo")


def _attention_full_core(cfg: ModelConfig, q, k, v, core: dict) -> jax.Array:
    """The attention of ``attention_full`` after the projections: the flex
    flash kernel, the jnp flash core, or that core under a context-parallel
    shard_map."""
    from repro.models.sharding import active_mesh, extent, spec_for

    B, S = q.shape[:2]
    mesh = active_mesh()
    ext = extent("act_seq")
    if mesh is None or ext <= 1 or S % ext:
        if (cfg.attn_pallas and core["causal"] and not core["window"]
                and not core["prefix_len"] and k.shape[1] == S):
            # the planned flex flash kernel (self-attention prefill shapes;
            # windowed/prefix/cross layers keep the jnp core)
            from repro.kernels.flash_attention import mha_flash
            from repro.kernels.ops import default_interpret

            sweep, (bq, bk) = _attn_schedule()
            return mha_flash(q, k, v, causal=True, block_q=bq, block_k=bk,
                             sweep=sweep, interpret=default_interpret())
        return _attention_core(cfg, q, k, v, q_offset=0, **core)
    from jax.sharding import PartitionSpec as P

    seq_axes = spec_for("act_seq")[0]
    dp = spec_for("act_batch")[0] if B % extent("act_batch") == 0 else None
    q_spec = P(dp, seq_axes, None, None)
    kv_spec = P(dp, None, None, None)
    Sloc = S // ext

    def local_fn(q_l, k_l, v_l):
        idx = jax.lax.axis_index(seq_axes)
        return _attention_core(cfg, q_l, k_l, v_l, q_offset=idx * Sloc, **core)

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=q_spec,
    )(q, k, v)


def _decode_core(q, k, v, kpos, pos, window: int, scale: float, axis: str | None):
    """Flash-style decode attention over a (possibly seq-sharded) cache.

    q (B,1,H,hd); k/v (B,Sloc,Hkv·hd) local shard, a position's heads side
    by side as the paged pools hold them; kpos global key positions.
    ``pos`` is a scalar (whole batch at one position) or a (B,) vector of
    per-slot positions (the continuous-batching paged path, where every slot
    is at a different depth in its own stream).
    With ``axis`` set (inside shard_map) the softmax is distributed:
    pmax for the max, psum for numerator/denominator — so a 32k..500k cache
    never gets gathered (observed: 40GB/step of cache all-gathers before).

    Heads are never split out of K/V's rows: the scores are one product of
    the block-diagonal query rows (``heads_to_rows``) with K, the output one
    product of the probabilities with V, of which each head keeps its own
    columns (``rows_to_heads``).  Both take their operands in the cache's
    dtype and accumulate in f32 (what the TPU's default precision computes
    for f32 operands), so no f32 copy of the cache is ever made.
    """
    from repro.kernels.flash_attention import (
        heads_to_rows,
        row_values_to_heads,
        rows_to_heads,
    )

    B, _, H, hd = q.shape
    Hkv = k.shape[-1] // hd
    q_rows = heads_to_rows(q[:, 0].astype(k.dtype), Hkv)
    s = jnp.einsum("bnc,bkc->bnk", q_rows, k, preferred_element_type=jnp.float32)
    s = s * scale
    if getattr(pos, "ndim", 0):
        m = kpos[None, :] <= pos[:, None]
        if window:
            m = m & ((pos[:, None] - kpos[None, :]) < window)
        s = jnp.where(m[:, None, :], s, -1e30)
    else:
        m = kpos <= pos
        if window:
            m = m & ((pos - kpos) < window)
        s = jnp.where(m[None, None, :], s, -1e30)
    mx = jnp.max(s, axis=-1, keepdims=True)
    if axis is not None:
        mx = jax.lax.pmax(mx, axis)
    pr = jnp.exp(s - mx)
    num = rows_to_heads(jnp.einsum("bnk,bkc->bnc", pr.astype(v.dtype), v,
                                   preferred_element_type=jnp.float32), hd)
    den = row_values_to_heads(jnp.sum(pr, axis=-1), Hkv)  # (B,H)
    if axis is not None:
        num = jax.lax.psum(num, axis)
        den = jax.lax.psum(den, axis)
    o = num / jnp.maximum(den, 1e-30)[..., None]
    return o.reshape(B, 1, H, hd).astype(q.dtype)


def attention_decode(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,
    cache: dict[str, jax.Array],
    pos: jax.Array,
    *,
    window: int = 0,
    use_rope: bool = True,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """One-token decode against a KV cache. x: (B, 1, D); cache k/v (B,Smax,Hkv,hd)."""
    from repro.models.sharding import active_mesh, extent, spec_for

    B, _, D = x.shape
    q, k_new, v_new = _project_qkv(cfg, p, x)
    if use_rope:
        q = rope(q, pos[None] if pos.ndim == 0 else pos, cfg.rope_theta)
        k_new = rope(k_new, pos[None] if pos.ndim == 0 else pos, cfg.rope_theta)
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new.astype(cache["k"].dtype), pos, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new.astype(cache["v"].dtype), pos, axis=1)
    Smax = k.shape[1]
    scale = 1.0 / math.sqrt(cfg.head_dim)

    mesh = active_mesh()
    ext = extent("act_seq")
    Hkv = cfg.num_kv_heads
    if mesh is None or ext <= 1 or Smax % ext or Hkv % ext == 0:
        # single-device, or the cache is head-sharded (divisible kv heads)
        with jax.named_scope("attn.core"):
            o = _decode_core(q, k.reshape(B, Smax, cfg.kv_dim),
                             v.reshape(B, Smax, cfg.kv_dim), jnp.arange(Smax),
                             pos, window, scale, None)
    else:
        from jax.sharding import PartitionSpec as P

        seq_ax = spec_for("act_seq")[0]
        dp = spec_for("act_batch")[0] if B % extent("act_batch") == 0 else None
        Sloc = Smax // ext

        def local_fn(q_l, k_l, v_l, pos_l):
            idx = jax.lax.axis_index(seq_ax)
            kpos = idx * Sloc + jnp.arange(Sloc)
            flat = (*k_l.shape[:2], -1)
            return _decode_core(q_l, k_l.reshape(flat), v_l.reshape(flat), kpos,
                                pos_l, window, scale, seq_ax)

        with jax.named_scope("attn.core"):
            o = jax.shard_map(
                local_fn, mesh=mesh,
                in_specs=(P(dp, None, None, None), P(dp, seq_ax, None, None),
                          P(dp, seq_ax, None, None), P()),
                out_specs=P(dp, None, None, None),
            )(q, k, v, pos)

    out = linear(cfg, o.reshape(B, 1, cfg.q_dim), p["wo"], name="attn.wo")
    return out, {"k": k, "v": v}


def init_kv_cache(cfg: ModelConfig, batch: int, seq: int, layers: int | None = None):
    L = layers if layers is not None else cfg.num_layers
    shape = (L, batch, seq, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, jnp.bfloat16), "v": jnp.zeros(shape, jnp.bfloat16)}


def attention_decode_paged(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,
    pk: jax.Array,
    pv: jax.Array,
    base: jax.Array,
    table: jax.Array,
    positions: jax.Array,
    *,
    window: int = 0,
    use_rope: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode against one layer's blocks of the stacked KV pools.

    x (B,1,D); pk/pv (L·num_blocks, bs, Hkv·hd) — every layer's block
    pools, flattened so that this layer's block ``b`` is row ``base + b``;
    table (B, nb) int32 per-slot block tables; positions (B,) per-slot write
    positions (= tokens already cached for that slot).  The new K/V lands at
    ``(base + table[pos // bs], pos % bs)`` per slot, in place, then
    attention runs over the gathered dense view of each slot's table with
    the per-slot causal mask of ``_decode_core``, which reads the view in
    the pools' rows, heads side by side.  Pad slots of a bucketed
    batch point their whole table at the reserved scratch block, so their
    writes land in this layer's scratch block, never in a live request's
    blocks, and their garbage reads are masked to exact zeros.
    """
    B, _, D = x.shape
    q, k_new, v_new = _project_qkv(cfg, p, x)
    if use_rope:
        q = rope(q, positions[:, None], cfg.rope_theta)
        k_new = rope(k_new, positions[:, None], cfg.rope_theta)
    bs = pk.shape[1]
    rows = base + table  # this layer's blocks, as rows of the stacked pools
    with jax.named_scope("kv.append"):
        blk = jnp.take_along_axis(rows, (positions // bs)[:, None], axis=1)[:, 0]
        off = positions % bs
        pk = pk.at[blk, off].set(k_new.reshape(B, cfg.kv_dim).astype(pk.dtype))
        pv = pv.at[blk, off].set(v_new.reshape(B, cfg.kv_dim).astype(pv.dtype))
    scale = 1.0 / math.sqrt(cfg.head_dim)
    if cfg.attn_pallas and _attn_decode_kind(B) == "paged":
        # in-place Pallas kernel: K/V blocks stream straight out of the
        # pools through the scalar-prefetched table — no dense gather copy
        from repro.kernels.flash_attention import paged_attention
        from repro.kernels.ops import default_interpret

        with jax.named_scope("attn.core"):
            o = paged_attention(q[:, 0], pk, pv, rows, positions, scale=scale,
                                window=window,
                                interpret=default_interpret())[:, None]
    else:
        # dense per-slot view: gathered entry j is the slot's logical
        # position j
        with jax.named_scope("attn.kv_gather"):
            k = pk[rows].reshape(B, -1, cfg.kv_dim)
            v = pv[rows].reshape(B, -1, cfg.kv_dim)
        with jax.named_scope("attn.core"):
            o = _decode_core(q, k, v, jnp.arange(k.shape[1]), positions, window,
                             scale, None)
    out = linear(cfg, o.reshape(B, 1, cfg.q_dim), p["wo"], name="attn.wo")
    return out, pk, pv


# ---------------------------------------------------------------------------
# MLP (gated + plain)
# ---------------------------------------------------------------------------


def init_mlp(cfg: ModelConfig, key, d_ff: int | None = None) -> Params:
    ks = split_keys(key, 3)
    D, F = cfg.d_model, d_ff or cfg.d_ff
    p = {"w1": _init(ks[0], (D, F)), "w2": _init(ks[1], (F, D))}
    if cfg.activation in ("silu", "gelu"):
        p["w3"] = _init(ks[2], (D, F))
    return p


def mlp(
    cfg: ModelConfig, p: Params, x: jax.Array, residual: jax.Array | None = None
) -> jax.Array:
    """Sequence-parallel FFN: the hidden stays SEQ-sharded (weights are
    gathered instead — the IS mesh dataflow).  Sharding the hidden on the
    feature dim would force a per-layer seq all-gather of x, which §Perf C3
    measured at ~70% of qwen3-train's entire collective term.

    The activation fuses into the w1 kernel and ``residual`` into the w2
    kernel on the pallas path, so the hidden/output never re-stream through
    HBM for the epilogue."""
    act = "silu" if cfg.activation == "silu" else "gelu"
    if "w3" in p:
        h = linear(cfg, x, p["w1"], activation=act, name="mlp.w1")
        h = h * linear(cfg, x, p["w3"], name="mlp.w3")
    else:
        h = linear(cfg, x, p["w1"], activation=act, name="mlp.w1")
    h = constrain(h, "act_batch", "act_seq", None)
    return linear(cfg, h, p["w2"], residual=residual, name="mlp.w2")


# ---------------------------------------------------------------------------
# Mixture of Experts (capacity-based, EP-sharded, no one-hot matmul dispatch)
# ---------------------------------------------------------------------------


def init_moe(cfg: ModelConfig, key) -> Params:
    ks = split_keys(key, 4)
    D, E, Fe = cfg.d_model, cfg.num_experts, cfg.expert_d_ff or cfg.d_ff
    p = {
        "router": _init(ks[0], (D, E), scale=0.02),
        "we1": _init(ks[1], (E, D, Fe)),
        "we2": _init(ks[2], (E, Fe, D)),
        "we3": _init(ks[3], (E, D, Fe)),
    }
    return p


def moe(cfg: ModelConfig, p: Params, x: jax.Array) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Top-k capacity-based MoE with *block-local* dispatch (GShard/Switch).

    Tokens are grouped into NB blocks aligned with the data-parallel mesh
    extent; each block scatters into its own (E, cap_local) slots, so the
    scatter/gather have a leading batch dim that GSPMD shards cleanly (no
    replication), and the block->expert resharding lowers to an all-to-all —
    the production EP pattern.  Dispatch avoids one-hot einsums so HLO FLOPs
    stay proportional to *active* parameters (DESIGN.md §6).
    """
    from repro.models.sharding import dp_size

    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.top_k
    T = B * S
    NB = dp_size()
    if T % NB or NB < 1:
        NB = 1
    Tl = T // NB
    xf = x.reshape(NB, Tl, D)
    xf = constrain(xf, "act_batch", None, None)

    # router einsum in model dtype (an f32 copy of xf is 3.8GB/device on the
    # 480B config); only the small (T, E) logits are upcast for the softmax
    logits = jnp.einsum("btd,de->bte", xf, p["router"].astype(xf.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)  # (NB, Tl, K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # per-block position of each (token, k) assignment within its expert's
    # capacity.  Small-T floor keeps decode/smoke paths drop-free; training
    # shapes (Tl >> 256) keep standard capacity-factor behaviour.
    cap = max(int(cfg.capacity_factor * Tl * K / E), 1, min(Tl, 256))
    flat_e = expert_idx.reshape(NB, Tl * K)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)          # (NB, TlK, E)
    pos_in_e = jnp.cumsum(onehot, axis=1) - onehot               # exclusive
    flat_pos = jnp.take_along_axis(pos_in_e, flat_e[..., None], axis=2)[..., 0]
    keep = flat_pos < cap
    safe_pos = jnp.where(keep, flat_pos, cap - 1)

    # dispatch: (NB, E, cap, D) — vmapped scatter over the block dim
    xk = jnp.repeat(xf[:, :, None, :], K, axis=2).reshape(NB, Tl * K, D)
    xk = jnp.where(keep[..., None], xk, 0)
    xk = constrain(xk, "act_batch", None, None)

    def scatter_block(xk_b, e_b, pos_b):
        return jnp.zeros((E, cap, D), xf.dtype).at[e_b, pos_b].add(xk_b)

    disp = jax.vmap(scatter_block)(xk, flat_e, safe_pos)
    disp = constrain(disp, "act_batch", "act_expert", None, None)  # all-to-all

    # expert FFN (einsum over expert-sharded params; NB is a batch dim)
    h1 = jnp.einsum("becd,edf->becf", disp, p["we1"].astype(disp.dtype))
    h3 = jnp.einsum("becd,edf->becf", disp, p["we3"].astype(disp.dtype))
    h = jax.nn.silu(h1) * h3
    h = constrain(h, "act_batch", "act_expert", None, None)
    eo = jnp.einsum("becf,efd->becd", h, p["we2"].astype(disp.dtype))
    eo = constrain(eo, "act_batch", "act_expert", None, None)

    # combine: vmapped gather back to block-local tokens
    def gather_block(eo_b, e_b, pos_b):
        return eo_b[e_b, pos_b]

    gathered = jax.vmap(gather_block)(eo, flat_e, safe_pos)  # (NB, TlK, D)
    gathered = constrain(gathered, "act_batch", None, None)
    gathered = jnp.where(keep[..., None], gathered, 0)
    gates = gate_vals.reshape(NB, Tl * K).astype(gathered.dtype)
    out = jnp.sum((gathered * gates[..., None]).reshape(NB, Tl, K, D), axis=2)

    # aux losses: load balance (Switch) + router z-loss
    me = jnp.mean(jax.nn.one_hot(expert_idx, E, dtype=jnp.float32).sum(2), axis=(0, 1))
    ce = jnp.mean(probs, axis=(0, 1))
    lb = E * jnp.sum(me * ce) / K
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return out.reshape(B, S, D), {"load_balance": lb, "router_z": z}
