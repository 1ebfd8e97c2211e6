"""Plain reference of a dense decoder (Qwen3, MiniCPM), from the published
descriptions, in float32 at ``highest`` matmul precision.

It imports nothing of the program.  It reads the weights that
``bench/weights.py`` made, in the layout that file states, and the
published configuration: pre-norm blocks with RMSNorm (``x * rsqrt(mean
x^2 + eps) * (1 + scale)``), grouped-query attention (query head ``h``
reads key/value head ``h // (H / Hkv)``) with rotary embeddings on the two
halves of each head, Qwen3's per-head RMSNorm of queries and keys before
the rotation, a causal softmax in float32, a SwiGLU feed-forward
(``down(silu(gate x) * up x)``), MiniCPM's ``scale_emb`` on the embedding
and ``scale_depth / sqrt(layers)`` on each block's output, and the tied
output head divided by ``hidden_size / dim_model_base`` (MiniCPM) or by 1.

``control=True`` computes the same function one precision below the
configuration's bfloat16: both operands of every projection, the output
head's included, rounded to float8 (e4m3), the weights with a scale per
output channel and the activations with a scale per row (each slice's
absolute maximum mapped to 448), products summed in float32, and every
other activation rounded to bfloat16 where the served model holds it in
bfloat16.  That is the step a later change could take to go faster.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_CHUNK = 256  # query rows per attention tile


def _head_divisor(c: dict) -> float:
    if "dim_model_base" in c:
        return float(c["hidden_size"]) / float(c["dim_model_base"])
    return 1.0


def _fp8(x, axis: int):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``
    (the slice's absolute maximum maps to 448), back in f32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def make_forward(c: dict, *, control: bool = False):
    """``fwd(weights, tokens (T,), start, n) -> logits (n, vocab)`` f32 at
    positions ``start .. start + n - 1`` of ``tokens``; ``T`` and ``n`` are
    static, ``start`` is traced.  Rows past the real sequence may hold any
    token: attention is causal, so they never reach a scored position."""
    L, D = int(c["num_hidden_layers"]), int(c["hidden_size"])
    H = int(c["num_attention_heads"])
    Hkv = int(c.get("num_key_value_heads", H))
    hd = int(c.get("head_dim") or D // H)
    g = H // Hkv
    V = int(c["vocab_size"])
    eps = float(c["rms_norm_eps"])
    theta = float(c.get("rope_theta", 10000.0))
    emb_scale = float(c.get("scale_emb", 1.0))
    res_scale = (float(c["scale_depth"]) / math.sqrt(L)
                 if "scale_depth" in c else 1.0)
    qk_norm = c.get("model_type") == "qwen3"
    div = _head_divisor(c)

    def act(x):  # where the served model holds an activation in bf16
        return x.astype(jnp.bfloat16).astype(jnp.float32) if control else x

    def weight(w):  # (..., K, N): a scale per output channel
        w = w.astype(jnp.float32)
        return _fp8(w, -2) if control else w

    def mm(x, w):  # (..., K) rows: a scale per row
        x = _fp8(act(x), -1) if control else x
        return act(jnp.dot(x, w, precision=HIGHEST))

    def rms(x, s):
        y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        return act(y * (1.0 + s.astype(jnp.float32)))

    def rope(x, pos):
        freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
        ang = pos[:, None].astype(jnp.float32) * freqs
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
        return act(jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1))

    def attention(q, k, v, T):
        # q (T, H, hd); k, v (T, Hkv, hd) -> (T, H * hd), causal
        kg = jnp.repeat(k, g, axis=1)
        vg = jnp.repeat(v, g, axis=1)
        kpos = jnp.arange(T)

        def tile(_, i0):
            qc = jax.lax.dynamic_slice_in_dim(q, i0, Q_CHUNK, 0)
            s = jnp.einsum("qhd,khd->hqk", qc, kg, precision=HIGHEST)
            s = s / math.sqrt(hd)
            qpos = i0 + jnp.arange(Q_CHUNK)
            s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("hqk,khd->qhd", p, vg, precision=HIGHEST)
            return None, o.reshape(Q_CHUNK, H * hd)

        _, o = jax.lax.scan(tile, None, jnp.arange(0, T, Q_CHUNK))
        return act(o.reshape(T, H * hd))

    def layer(x, lw):
        T = x.shape[0]
        pos = jnp.arange(T)
        a = lw["attn"]
        h = rms(x, lw["ln1"]["scale"])
        q = mm(h, weight(a["wq"])).reshape(T, H, hd)
        k = mm(h, weight(a["wk"])).reshape(T, Hkv, hd)
        v = mm(h, weight(a["wv"])).reshape(T, Hkv, hd)
        if qk_norm:
            q, k = rms(q, a["q_norm"]), rms(k, a["k_norm"])
        q, k = rope(q, pos), rope(k, pos)
        o = mm(attention(q, k, v, T), weight(a["wo"]))
        x = act(x + res_scale * o)
        m = lw["mlp"]
        h = rms(x, lw["ln2"]["scale"])
        f = act(jax.nn.silu(mm(h, weight(m["w1"])))) * mm(h, weight(m["w3"]))
        x = act(x + res_scale * mm(act(f), weight(m["w2"])))
        return x, None

    def fwd(weights, tokens, start, n: int):
        T = tokens.shape[0]
        if T % Q_CHUNK:
            raise ValueError(f"sequence of {T} rows is not a multiple of {Q_CHUNK}")
        emb = weights["embed"]
        x = act(emb[tokens].astype(jnp.float32) * emb_scale)
        x, _ = jax.lax.scan(layer, x, weights["layers"])
        h = rms(jax.lax.dynamic_slice_in_dim(x, start, n, 0),
                weights["final_norm"]["scale"])
        if "lm_head" in weights:
            head = weight(weights["lm_head"])[:, :V]
        else:
            head = weight(emb[:V].astype(jnp.float32).T)
        return mm(h, head) / div

    return jax.jit(fwd, static_argnames=("n",))
