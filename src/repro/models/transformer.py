"""Model assembly for all ten architectures.

One functional ``Model`` facade with family-specific forward / prefill /
decode paths.  Layer stacks run under ``jax.lax.scan`` over *groups* of
``len(cfg.window_pattern)`` layers so per-layer static sliding windows
(gemma3's 5 local : 1 global) coexist with scan's compact HLO.  Params are
nested dicts; stacked layer params carry a leading (num_groups, group_size)
pair of axes.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models import layers as Lyr
from repro.models import ssm as S
from repro.models.config import ModelConfig
from repro.models.sharding import constrain

Params = dict[str, Any]


def _stack(trees: list[Params]) -> Params:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _group(stacked: Params, groups: int, per: int) -> Params:
    return jax.tree.map(lambda a: a.reshape(groups, per, *a.shape[1:]), stacked)


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    pol = None
    if policy == "dots":
        pol = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    return jax.checkpoint(fn, policy=pol)


# ---------------------------------------------------------------------------
# dense / moe decoder blocks
# ---------------------------------------------------------------------------


def init_block(cfg: ModelConfig, key) -> Params:
    ks = Lyr.split_keys(key, 4)
    p: Params = {
        "ln1": Lyr.init_norm(cfg, ks[0]),
        "attn": Lyr.init_attention(cfg, ks[1]),
        "ln2": Lyr.init_norm(cfg, ks[2]),
    }
    if cfg.family == "moe":
        p["moe"] = Lyr.init_moe(cfg, ks[3])
        if cfg.moe_dense_ff:
            p["mlp"] = Lyr.init_mlp(cfg, Lyr.split_keys(ks[3], 2)[1], cfg.moe_dense_ff)
    else:
        p["mlp"] = Lyr.init_mlp(cfg, ks[3])
    return p


def block_apply(
    cfg: ModelConfig, p: Params, x: jax.Array, window: int, prefix_len: int = 0
) -> tuple[jax.Array, dict[str, jax.Array]]:
    # With unit residual scale the residual adds fuse into the wo / w2
    # projection kernels (pallas path) instead of separate XLA adds.
    fuse_res = cfg.residual_scale == 1.0
    h = Lyr.norm(cfg, p["ln1"], x)
    h = Lyr.attention_full(cfg, p["attn"], h, window=window, prefix_len=prefix_len,
                           residual=x if fuse_res else None)
    x = h if fuse_res else x + cfg.residual_scale * h
    h = Lyr.norm(cfg, p["ln2"], x)
    aux = {"load_balance": jnp.zeros((), jnp.float32), "router_z": jnp.zeros((), jnp.float32)}
    if "moe" in p:
        mo, aux = Lyr.moe(cfg, p["moe"], h)
        if "mlp" in p:
            mo = mo + Lyr.mlp(cfg, p["mlp"], h)
        x = x + cfg.residual_scale * mo
    else:
        mo = Lyr.mlp(cfg, p["mlp"], h, residual=x if fuse_res else None)
        x = mo if fuse_res else x + cfg.residual_scale * mo
    x = constrain(x, "act_batch", "act_seq", "act_embed")
    return x, aux


def block_decode(
    cfg: ModelConfig, p: Params, x: jax.Array, cache: Params, pos: jax.Array, window: int
) -> tuple[jax.Array, Params]:
    h = Lyr.norm(cfg, p["ln1"], x)
    h, cache = Lyr.attention_decode(cfg, p["attn"], h, cache, pos, window=window)
    x = x + cfg.residual_scale * h
    h = Lyr.norm(cfg, p["ln2"], x)
    if "moe" in p:
        mo, _ = Lyr.moe(cfg, p["moe"], h)
        if "mlp" in p:
            mo = mo + Lyr.mlp(cfg, p["mlp"], h)
    else:
        mo = Lyr.mlp(cfg, p["mlp"], h)
    return x + cfg.residual_scale * mo, cache


def block_decode_paged(
    cfg: ModelConfig, p: Params, x: jax.Array, pk: jax.Array, pv: jax.Array,
    base: jax.Array, table: jax.Array, positions: jax.Array, window: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``block_decode`` against one layer's blocks of the flattened stacked
    block pools, rows ``base + table`` (per-slot positions)."""
    h = Lyr.norm(cfg, p["ln1"], x)
    h, pk, pv = Lyr.attention_decode_paged(
        cfg, p["attn"], h, pk, pv, base, table, positions, window=window)
    x = x + cfg.residual_scale * h
    h = Lyr.norm(cfg, p["ln2"], x)
    if "moe" in p:
        mo, _ = Lyr.moe(cfg, p["moe"], h)
        if "mlp" in p:
            mo = mo + Lyr.mlp(cfg, p["mlp"], h)
    else:
        mo = Lyr.mlp(cfg, p["mlp"], h)
    return x + cfg.residual_scale * mo, pk, pv


# ---------------------------------------------------------------------------
# the Model facade
# ---------------------------------------------------------------------------


class Model:
    def __init__(self, cfg: ModelConfig, remat: str = "none", unroll: bool = False):
        self.cfg = cfg
        self.remat = remat
        self.unroll = unroll  # python-loop layer stacks (exact HLO cost probes)
        self.dtype = jnp.dtype(cfg.dtype)
        pat = len(cfg.window_pattern)
        if cfg.family in ("dense", "moe", "vlm") and cfg.num_layers % pat:
            raise ValueError(f"{cfg.num_layers} layers not divisible by pattern {pat}")

    def _scan(self, body, carry, xs):
        """lax.scan, or an unrolled python loop when cost probing."""
        if not self.unroll:
            return jax.lax.scan(body, carry, xs)
        L = jax.tree.leaves(xs)[0].shape[0]
        ys = []
        for i in range(L):
            xi = jax.tree.map(lambda a, i=i: a[i], xs)
            carry, y = body(carry, xi)
            ys.append(y)
        if all(y is None for y in ys):
            return carry, None
        return carry, jax.tree.map(lambda *a: jnp.stack(a), *ys)

    # -- init ---------------------------------------------------------------
    def init(self, key) -> Params:
        cfg = self.cfg
        ks = Lyr.split_keys(key, 8)
        params: Params = {
            "embed": Lyr._init(ks[0], (cfg.padded_vocab, cfg.d_model), scale=0.02),
            "final_norm": Lyr.init_norm(cfg, ks[1]),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = Lyr._init(ks[2], (cfg.d_model, cfg.padded_vocab), scale=0.02)

        if cfg.family in ("dense", "moe", "vlm"):
            # one vmapped draw per leaf (the same values as stacking
            # per-layer draws) keeps the traced init independent of depth
            params["layers"] = jax.vmap(lambda k: init_block(cfg, k))(
                jax.random.split(ks[3], cfg.num_layers))
            if cfg.family == "vlm":
                vin = cfg.vision_embed_dim or cfg.d_model
                params["vision_proj"] = Lyr._init(ks[4], (vin, cfg.d_model))
        elif cfg.family == "ssm":
            blocks = []
            for k in Lyr.split_keys(ks[3], cfg.num_layers):
                k1, k2, k3, k4 = Lyr.split_keys(k, 4)
                blocks.append(
                    {
                        "ln1": Lyr.init_norm(cfg, k1),
                        "tmix": S.init_rwkv6(cfg, k2),
                        "ln2": Lyr.init_norm(cfg, k3),
                    }
                )
            params["layers"] = _stack(blocks)
        elif cfg.family == "hybrid":
            blocks = []
            for k in Lyr.split_keys(ks[3], cfg.num_layers):
                k1, k2 = Lyr.split_keys(k, 2)
                blocks.append({"ln1": Lyr.init_norm(cfg, k1), "mamba": S.init_mamba2(cfg, k2)})
            params["layers"] = _stack(blocks)
            params["shared_attn"] = init_block(cfg.replace(family="dense"), ks[4])
        elif cfg.family == "encdec":
            enc_cfg = cfg
            params["enc_layers"] = _stack(
                [init_block(cfg.replace(family="dense"), k)
                 for k in Lyr.split_keys(ks[3], cfg.num_enc_layers)]
            )
            params["enc_norm"] = Lyr.init_norm(cfg, ks[4])
            dec = []
            for k in Lyr.split_keys(ks[5], cfg.num_layers):
                k1, k2, k3, k4, k5, k6 = Lyr.split_keys(k, 6)
                dec.append(
                    {
                        "ln1": Lyr.init_norm(cfg, k1),
                        "attn": Lyr.init_attention(cfg, k2),
                        "ln_x": Lyr.init_norm(cfg, k3),
                        "xattn": Lyr.init_attention(cfg, k4),
                        "ln2": Lyr.init_norm(cfg, k5),
                        "mlp": Lyr.init_mlp(cfg, k6),
                    }
                )
            params["layers"] = _stack(dec)
            params["dec_pos"] = Lyr._init(ks[6], (cfg.max_seq_len, cfg.d_model), scale=0.02)
        pdt = jnp.dtype(cfg.param_dtype)
        if pdt != jnp.float32:
            params = jax.tree.map(lambda a: a.astype(pdt), params)
        return params

    # -- shared helpers -------------------------------------------------------
    def _embed(self, params, tokens):
        x = params["embed"].astype(self.dtype)[tokens] * self.cfg.emb_scale
        return constrain(x, "act_batch", "act_seq", "act_embed")

    def _logits(self, params, h):
        cfg = self.cfg
        h = Lyr.norm(cfg, params["final_norm"], h)
        wout = params.get("lm_head")
        if wout is None:
            wout = params["embed"].T
        logits = Lyr.linear(cfg, h, wout, name="lm_head").astype(jnp.float32)
        if cfg.logit_divisor != 1.0:
            logits = logits / cfg.logit_divisor
        if cfg.logit_softcap:
            logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
        # vocab (not seq) carries the 'model' axis here — the two must not collide
        return constrain(logits, "act_batch", "act_seq_np", "act_vocab")

    # -- dense/moe/vlm stack --------------------------------------------------
    def _stack_forward(self, params, x, prefix_len=0):
        cfg = self.cfg
        pat = len(cfg.window_pattern)
        groups = cfg.num_layers // pat
        gp = _group(params["layers"], groups, pat)

        def body(carry, lp):
            x, lb, rz = carry
            for j in range(pat):
                pj = jax.tree.map(lambda a, j=j: a[j], lp)
                x, aux = block_apply(cfg, pj, x, cfg.window_pattern[j], prefix_len)
                lb, rz = lb + aux["load_balance"], rz + aux["router_z"]
            return (x, lb, rz), None

        body = _remat(body, self.remat)
        (x, lb, rz), _ = self._scan(body, (x, jnp.zeros(()), jnp.zeros(())), gp)
        return x, {"load_balance": lb / cfg.num_layers, "router_z": rz / cfg.num_layers}

    # -- rwkv stack -------------------------------------------------------------
    def _rwkv_forward(self, params, x):
        cfg = self.cfg

        def body(carry, lp):
            x, = carry
            h, _ = S.rwkv6_time_mix(cfg, lp["tmix"], Lyr.norm(cfg, lp["ln1"], x))
            x = x + h
            h, _ = S.rwkv6_channel_mix(cfg, lp["tmix"], Lyr.norm(cfg, lp["ln2"], x))
            x = x + h
            x = constrain(x, "act_batch", "act_seq", "act_embed")
            return (x,), None

        body = _remat(body, self.remat)
        (x,), _ = self._scan(body, (x,), params["layers"])
        return x, {}

    # -- hybrid (zamba2) stack ---------------------------------------------------
    def _hybrid_forward(self, params, x):
        cfg = self.cfg
        flags = jnp.array(
            [(i % cfg.attn_every == cfg.attn_every - 1) for i in range(cfg.num_layers)]
        )
        shared = params["shared_attn"]

        def body(carry, inp):
            x, = carry
            lp, flag = inp
            h, _ = S.mamba2(cfg, lp["mamba"], Lyr.norm(cfg, lp["ln1"], x))
            x = x + h

            def with_attn(x):
                y, _ = block_apply(cfg, shared, x, window=0)
                return y

            x = jax.lax.cond(flag, with_attn, lambda x: x, x)
            x = constrain(x, "act_batch", "act_seq", "act_embed")
            return (x,), None

        body = _remat(body, self.remat)
        (x,), _ = self._scan(body, (x,), (params["layers"], flags))
        return x, {}

    # -- encdec (whisper) ---------------------------------------------------------
    def _encode(self, params, audio_embeds):
        cfg = self.cfg
        x = audio_embeds.astype(self.dtype)
        x = x + Lyr.sinusoidal_pos(x.shape[1], cfg.d_model).astype(x.dtype)[None]
        x = constrain(x, "act_batch", "act_seq", "act_embed")

        def body(carry, lp):
            x, = carry
            h = Lyr.norm(cfg, lp["ln1"], x)
            h = Lyr.attention_full(cfg, lp["attn"], h, causal=False, use_rope=False)
            x = x + h
            h = Lyr.norm(cfg, lp["ln2"], x)
            x = x + Lyr.mlp(cfg, lp["mlp"], h)
            return (constrain(x, "act_batch", "act_seq", "act_embed"),), None

        body = _remat(body, self.remat)
        (x,), _ = self._scan(body, (x,), params["enc_layers"])
        return Lyr.norm(cfg, params["enc_norm"], x)

    def _decode_stack(self, params, tokens, enc_out):
        cfg = self.cfg
        B, Sq = tokens.shape
        x = params["embed"].astype(self.dtype)[tokens]
        x = x + params["dec_pos"][:Sq].astype(x.dtype)[None]
        x = constrain(x, "act_batch", "act_seq", "act_embed")

        def body(carry, lp):
            x, = carry
            h = Lyr.norm(cfg, lp["ln1"], x)
            h = Lyr.attention_full(cfg, lp["attn"], h, use_rope=False)
            x = x + h
            h = Lyr.norm(cfg, lp["ln_x"], x)
            h = Lyr.attention_full(cfg, lp["xattn"], h, causal=False, xkv=enc_out, use_rope=False)
            x = x + h
            h = Lyr.norm(cfg, lp["ln2"], x)
            x = x + Lyr.mlp(cfg, lp["mlp"], h)
            return (constrain(x, "act_batch", "act_seq", "act_embed"),), None

        body = _remat(body, self.remat)
        (x,), _ = self._scan(body, (x,), params["layers"])
        return x

    # -- public: training forward --------------------------------------------------
    def forward(self, params: Params, batch: dict[str, jax.Array]):
        """Returns (logits, aux). batch keys depend on family (see input_specs)."""
        cfg = self.cfg
        if cfg.family in ("dense", "moe"):
            x = self._embed(params, batch["tokens"])
            h, aux = self._stack_forward(params, x)
        elif cfg.family == "vlm":
            vis = jnp.einsum(
                "bsd,de->bse", batch["vision_embeds"].astype(self.dtype),
                params["vision_proj"].astype(self.dtype),
            )
            txt = self._embed(params, batch["tokens"])
            x = jnp.concatenate([vis, txt], axis=1)
            x = constrain(x, "act_batch", "act_seq", "act_embed")
            h, aux = self._stack_forward(params, x, prefix_len=cfg.vision_tokens)
            h = h[:, cfg.vision_tokens :]
        elif cfg.family == "ssm":
            x = self._embed(params, batch["tokens"])
            h, aux = self._rwkv_forward(params, x)
        elif cfg.family == "hybrid":
            x = self._embed(params, batch["tokens"])
            h, aux = self._hybrid_forward(params, x)
        elif cfg.family == "encdec":
            enc = self._encode(params, batch["audio_embeds"])
            h = self._decode_stack(params, batch["tokens"], enc)
            aux = {}
        else:
            raise ValueError(cfg.family)
        return self._logits(params, h), aux

    def loss(self, params: Params, batch: dict[str, jax.Array]):
        if self.cfg.attn_pallas:
            raise ValueError(
                "attn_pallas is forward/serve only: the flex flash-attention "
                "kernels define no VJP. Train with attn_pallas=False.")
        logits, aux = self.forward(params, batch)
        labels = batch["labels"]
        mask = (labels >= 0).astype(jnp.float32)
        lab = jnp.maximum(labels, 0)
        lse = jax.nn.logsumexp(logits, axis=-1)
        # one-hot contraction instead of take_along_axis: reduces over the
        # vocab-sharded axis without gathering full-vocab logit rows
        onehot = jax.nn.one_hot(lab, logits.shape[-1], dtype=logits.dtype)
        gold = jnp.einsum("bsv,bsv->bs", logits, onehot)
        nll = (lse - gold) * mask
        loss = nll.sum() / jnp.maximum(mask.sum(), 1.0)
        if aux:
            loss = loss + 1e-2 * aux.get("load_balance", 0.0) + 1e-3 * aux.get("router_z", 0.0)
        return loss, {"nll": loss, **{k: v for k, v in aux.items()}}

    # -- public: serving -------------------------------------------------------------
    def prefill(self, params: Params, batch: dict[str, jax.Array], cache_len: int):
        """Run the prompt, build decode caches. Returns (cache, last_logits)."""
        cfg = self.cfg
        if cfg.family in ("dense", "moe", "vlm"):
            return self._prefill_dense(params, batch, cache_len)
        if cfg.family == "ssm":
            return self._prefill_rwkv(params, batch)
        if cfg.family == "hybrid":
            return self._prefill_hybrid(params, batch, cache_len)
        if cfg.family == "encdec":
            return self._prefill_encdec(params, batch, cache_len)
        raise ValueError(cfg.family)

    def prefill_kv(self, params, batch):
        """Forward the prompt and return ``(logits, k_all, v_all)`` with
        k/v stacked ``(L, B, Sp, Hkv·hd)`` bf16 — a position's heads side by
        side, as the projection yields them and the paged pools hold them.

        This is the layout-agnostic half of prefill: ``_prefill_dense``
        copies the K/V into a dense ``(L, B, cache_len, ...)`` cache, while
        the paged serving path (``launch.scheduler``) scatters it into KV
        block pools through a block table instead.  Dense/moe/vlm only.
        """
        cfg = self.cfg
        if cfg.family not in ("dense", "moe", "vlm"):
            raise NotImplementedError(f"prefill_kv covers dense/moe/vlm, not {cfg.family}")
        prefix = cfg.vision_tokens if cfg.family == "vlm" else 0
        if cfg.family == "vlm":
            vis = jnp.einsum(
                "bsd,de->bse", batch["vision_embeds"].astype(self.dtype),
                params["vision_proj"].astype(self.dtype),
            )
            x = jnp.concatenate([vis, self._embed(params, batch["tokens"])], axis=1)
        else:
            x = self._embed(params, batch["tokens"])
        B, Sp, _ = x.shape
        pat = len(cfg.window_pattern)
        gp = _group(params["layers"], cfg.num_layers // pat, pat)

        def body(carry, inp):
            x, = carry
            lp, gi = inp
            ks, vs = [], []
            for j in range(pat):
                with jax.named_scope("weights"):  # this layer's weight slabs
                    pj = jax.tree.map(lambda a, j=j: a[j], lp)
                h = Lyr.norm(cfg, pj["ln1"], x)
                q, k, v = Lyr._project_qkv(cfg, pj["attn"], h)
                k = Lyr.rope(k, jnp.arange(Sp), cfg.rope_theta)
                ks.append(k.astype(jnp.bfloat16))
                vs.append(v.reshape(B, Sp, cfg.kv_dim).astype(jnp.bfloat16))
                x, _ = block_apply(cfg, pj, x, cfg.window_pattern[j], prefix)
            return (x,), (jnp.stack(ks), jnp.stack(vs))

        (x,), (k_all, v_all) = self._scan(body, (x,), (gp, jnp.arange(cfg.num_layers // pat)))
        # K leaves the layer scan in rope's per-head layout and is flattened
        # here, once for all layers: flattening it inside the scan splits
        # rope's fusion into half-head pieces, which on the TPU cost a
        # 2048-token prefill ~3% (V is flat as the projection yields it)
        k_all = k_all.reshape(cfg.num_layers, B, Sp, cfg.kv_dim)
        v_all = v_all.reshape(cfg.num_layers, B, Sp, cfg.kv_dim)
        # logits come off the same pass: the scan's x walks through the exact
        # ``block_apply`` sequence ``forward`` uses, so final-norm + lm_head
        # here is bitwise-identical to a separate forward — at half the cost.
        logits = self._logits(params, x[:, prefix:] if prefix else x)
        return logits, k_all, v_all

    def _prefill_dense(self, params, batch, cache_len):
        cfg = self.cfg
        logits, k_all, v_all = self.prefill_kv(params, batch)
        L, B, Sp = k_all.shape[:3]
        heads = (L, B, Sp, cfg.num_kv_heads, cfg.head_dim)
        k_all, v_all = k_all.reshape(heads), v_all.reshape(heads)
        cache = Lyr.init_kv_cache(cfg, B, cache_len)
        cache["k"] = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_all, 0, axis=2)
        cache["v"] = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_all, 0, axis=2)
        return {"kv": cache, "pos": jnp.array(Sp, jnp.int32)}, logits[:, -1]

    def _prefill_rwkv(self, params, batch):
        cfg = self.cfg

        def body(carry, lp):
            x, = carry
            # the exact block-forward op sequence — ``return_state=True``
            # captures the final {shift, wkv} states the chunked scan already
            # computes, so prefill logits stay bitwise equal to ``forward``
            # (this used to be a 40-line drift-prone copy of the time mix)
            h = Lyr.norm(cfg, lp["ln1"], x)
            to, st1 = S.rwkv6_time_mix(cfg, lp["tmix"], h, return_state=True)
            x = x + to
            h2 = Lyr.norm(cfg, lp["ln2"], x)
            co, st2 = S.rwkv6_channel_mix(cfg, lp["tmix"], h2, return_state=True)
            x = x + co
            x = constrain(x, "act_batch", "act_seq", "act_embed")
            return (x,), {**st1, **st2}

        (h,), states = self._scan(body, (self._embed(params, batch["tokens"]),), params["layers"])
        logits = self._logits(params, h)
        return {"states": states, "pos": jnp.array(batch["tokens"].shape[1], jnp.int32)}, logits[:, -1]

    def _prefill_hybrid(self, params, batch, cache_len):
        cfg = self.cfg
        x = self._embed(params, batch["tokens"])
        B, T, _ = x.shape
        n_attn = sum(1 for i in range(cfg.num_layers) if i % cfg.attn_every == cfg.attn_every - 1)
        kv = Lyr.init_kv_cache(cfg, B, cache_len, layers=n_attn)
        flags = jnp.array([(i % cfg.attn_every == cfg.attn_every - 1) for i in range(cfg.num_layers)])
        slots = jnp.cumsum(flags) - 1
        shared = params["shared_attn"]

        def body(carry, inp):
            x, kv_k, kv_v = carry
            lp, flag, slot = inp
            h = Lyr.norm(cfg, lp["ln1"], x)
            B, T, D = h.shape
            # one mamba pass per layer: the chunked scan's final state comes
            # back through ``return_state`` (this used to re-run the whole
            # layer a second time just to recompute it)
            ho, st = S.mamba2(cfg, lp["mamba"], h, return_state=True)
            x = x + ho

            def with_attn(args):
                x, kv_k, kv_v = args
                hh = Lyr.norm(cfg, shared["ln1"], x)
                q, k, v = Lyr._project_qkv(cfg, shared["attn"], hh)
                k = Lyr.rope(k, jnp.arange(T), cfg.rope_theta)
                y, _ = block_apply(cfg, shared, x, window=0)
                zeros = jnp.zeros((1,) + kv_k.shape[1:], kv_k.dtype)
                k_pad = jax.lax.dynamic_update_slice(zeros, k[None].astype(kv_k.dtype), (0, 0, 0, 0, 0))
                v_pad = jax.lax.dynamic_update_slice(zeros, v[None].astype(kv_v.dtype), (0, 0, 0, 0, 0))
                kv_k = jax.lax.dynamic_update_slice(kv_k, k_pad, (slot, 0, 0, 0, 0))
                kv_v = jax.lax.dynamic_update_slice(kv_v, v_pad, (slot, 0, 0, 0, 0))
                return y, kv_k, kv_v

            x, kv_k, kv_v = jax.lax.cond(flag, with_attn, lambda a: a, (x, kv_k, kv_v))
            x = constrain(x, "act_batch", "act_seq", "act_embed")
            return (x, kv_k, kv_v), st

        (h, kv_k, kv_v), mstates = self._scan(
            body, (x, kv["k"], kv["v"]), (params["layers"], flags, slots)
        )
        logits = self._logits(params, h)
        return (
            {"mamba": mstates, "kv": {"k": kv_k, "v": kv_v}, "pos": jnp.array(T, jnp.int32)},
            logits[:, -1],
        )

    def _prefill_encdec(self, params, batch, cache_len):
        cfg = self.cfg
        enc = self._encode(params, batch["audio_embeds"])
        tokens = batch["tokens"]
        h = self._decode_stack(params, tokens, enc)
        logits = self._logits(params, h)
        B, Sp = tokens.shape
        cache = Lyr.init_kv_cache(cfg, B, cache_len)
        # self-attn K/V for the prompt + cross K/V from encoder output
        x = params["embed"].astype(self.dtype)[tokens] + params["dec_pos"][:Sp].astype(self.dtype)[None]

        def body(carry, lp):
            x, = carry
            h = Lyr.norm(cfg, lp["ln1"], x)
            _, k, v = Lyr._project_qkv(cfg, lp["attn"], h)
            hx = Lyr.norm(cfg, lp["ln_x"], x)
            _, xk, xv = Lyr._project_qkv(cfg, lp["xattn"], hx, enc)
            h2 = Lyr.attention_full(cfg, lp["attn"], h, use_rope=False)
            x = x + h2
            hx2 = Lyr.norm(cfg, lp["ln_x"], x)
            x = x + Lyr.attention_full(cfg, lp["xattn"], hx2, causal=False, xkv=enc, use_rope=False)
            h3 = Lyr.norm(cfg, lp["ln2"], x)
            x = x + Lyr.mlp(cfg, lp["mlp"], h3)
            return (x,), (k.astype(jnp.bfloat16), v.astype(jnp.bfloat16), xk.astype(jnp.bfloat16), xv.astype(jnp.bfloat16))

        (_,), (ks, vs, xks, xvs) = self._scan(body, (x,), params["layers"])
        cache["k"] = jax.lax.dynamic_update_slice_in_dim(cache["k"], ks, 0, axis=2)
        cache["v"] = jax.lax.dynamic_update_slice_in_dim(cache["v"], vs, 0, axis=2)
        return (
            {"kv": cache, "cross_k": xks, "cross_v": xvs, "pos": jnp.array(Sp, jnp.int32)},
            logits[:, -1],
        )

    # -- public: one-token decode ------------------------------------------------------
    def decode_step(self, params: Params, cache, token: jax.Array):
        """token: (B,) int32. Returns (logits (B,V), new_cache)."""
        cfg = self.cfg
        pos = cache["pos"]
        x = params["embed"].astype(self.dtype)[token][:, None] * cfg.emb_scale
        if cfg.family in ("dense", "moe", "vlm"):
            # The full cache rides the scan CARRY and is updated in place with
            # per-(layer, pos) dynamic_update_slice — scan-stacked ys would
            # defeat buffer donation and double the multi-GB cache in HBM
            # (observed: +6-18GB temp per decode step before this change).
            pat = len(cfg.window_pattern)
            groups = cfg.num_layers // pat
            gp = _group(params["layers"], groups, pat)

            def gbody(carry, inp):
                x, kv_k, kv_v = carry
                lp, g = inp
                for j in range(pat):
                    pj = jax.tree.map(lambda a, j=j: a[j], lp)
                    li = g * pat + j
                    kc = jax.lax.dynamic_index_in_dim(kv_k, li, 0, keepdims=False)
                    vc = jax.lax.dynamic_index_in_dim(kv_v, li, 0, keepdims=False)
                    x, c = block_decode(
                        cfg, pj, x, {"k": kc, "v": vc}, pos,
                        window=cfg.window_pattern[j],
                    )
                    kv_k = jax.lax.dynamic_update_index_in_dim(kv_k, c["k"], li, 0)
                    kv_v = jax.lax.dynamic_update_index_in_dim(kv_v, c["v"], li, 0)
                return (x, kv_k, kv_v), None

            (x, nk, nv), _ = self._scan(
                gbody, (x, cache["kv"]["k"], cache["kv"]["v"]),
                (gp, jnp.arange(groups)),
            )
            logits = self._logits(params, x)[:, 0]
            return logits, {"kv": {"k": nk, "v": nv}, "pos": pos + 1}

        if cfg.family == "ssm":
            def body(carry, inp):
                x, = carry
                lp, st = inp
                h = Lyr.norm(cfg, lp["ln1"], x)
                ho, st1 = S.rwkv6_time_mix(cfg, lp["tmix"], h, state={"shift_t": st["shift_t"], "wkv": st["wkv"]})
                x = x + ho
                h2 = Lyr.norm(cfg, lp["ln2"], x)
                co, st2 = S.rwkv6_channel_mix(cfg, lp["tmix"], h2, state={"shift_c": st["shift_c"]})
                x = x + co
                return (x,), {**st1, **st2}

            (x,), states = self._scan(body, (x,), (params["layers"], cache["states"]))
            logits = self._logits(params, x)[:, 0]
            return logits, {"states": states, "pos": pos + 1}

        if cfg.family == "hybrid":
            # scan over layers; shared-attn block applied via lax.cond on the
            # scanned flag, its KV cache carried whole with a scanned slot idx
            flags = jnp.array(
                [(i % cfg.attn_every == cfg.attn_every - 1) for i in range(cfg.num_layers)]
            )
            slots = jnp.cumsum(flags) - 1
            sh = params["shared_attn"]

            def body(carry, inp):
                x, kv_k, kv_v = carry
                lp, st, flag, slot = inp
                h = Lyr.norm(cfg, lp["ln1"], x)
                ho, st2 = S.mamba2(cfg, lp["mamba"], h, state=st)
                x = x + ho

                def with_attn(args):
                    x, kv_k, kv_v = args
                    hh = Lyr.norm(cfg, sh["ln1"], x)
                    kc = jax.lax.dynamic_index_in_dim(kv_k, slot, 0, keepdims=False)
                    vc = jax.lax.dynamic_index_in_dim(kv_v, slot, 0, keepdims=False)
                    ha, c = Lyr.attention_decode(cfg, sh["attn"], hh, {"k": kc, "v": vc}, pos)
                    y = x + ha
                    h2 = Lyr.norm(cfg, sh["ln2"], y)
                    y = y + Lyr.mlp(cfg, sh["mlp"], h2)
                    kv_k = jax.lax.dynamic_update_index_in_dim(kv_k, c["k"], slot, 0)
                    kv_v = jax.lax.dynamic_update_index_in_dim(kv_v, c["v"], slot, 0)
                    return y, kv_k, kv_v

                x, kv_k, kv_v = jax.lax.cond(flag, with_attn, lambda a: a, (x, kv_k, kv_v))
                return (x, kv_k, kv_v), st2

            (x, nk, nv), mstack = self._scan(
                body,
                (x, cache["kv"]["k"], cache["kv"]["v"]),
                (params["layers"], cache["mamba"], flags, slots),
            )
            logits = self._logits(params, x)[:, 0]
            return logits, {"mamba": mstack, "kv": {"k": nk, "v": nv}, "pos": pos + 1}

        if cfg.family == "encdec":
            x = x + params["dec_pos"][pos][None, None].astype(x.dtype)

            def body(carry, inp):
                x, = carry
                lp, kc, vc, xk, xv = inp
                h = Lyr.norm(cfg, lp["ln1"], x)
                ha, c = Lyr.attention_decode(cfg, lp["attn"], h, {"k": kc, "v": vc}, pos, use_rope=False)
                x = x + ha
                hx = Lyr.norm(cfg, lp["ln_x"], x)
                q, _, _ = Lyr._project_qkv(cfg, lp["xattn"], hx)
                import math as _m
                o = Lyr._sdpa(q, xk, xv, jnp.ones((1, 1, 1, xk.shape[1]), bool), 1.0 / _m.sqrt(cfg.head_dim))
                D = cfg.d_model
                x = x + jnp.einsum(
                    "bshd,hdD->bsD", o,
                    lp["xattn"]["wo"].astype(x.dtype).reshape(cfg.num_heads, cfg.head_dim, D),
                )
                h2 = Lyr.norm(cfg, lp["ln2"], x)
                x = x + Lyr.mlp(cfg, lp["mlp"], h2)
                return (x,), (c["k"], c["v"])

            (x,), (nk, nv) = self._scan(
                body, (x,),
                (params["layers"], cache["kv"]["k"], cache["kv"]["v"], cache["cross_k"], cache["cross_v"]),
            )
            logits = self._logits(params, x)[:, 0]
            return logits, {**cache, "kv": {"k": nk, "v": nv}, "pos": pos + 1}

        raise ValueError(cfg.family)

    def decode_step_paged(self, params: Params, pools, table, positions, token: jax.Array):
        """One continuous-batching decode step over the paged KV block pools.

        pools {"k","v"}: (L, num_blocks, bs, Hkv·hd); table (B, nb) int32
        per-slot block tables; positions (B,) int32 per-slot write positions;
        token (B,) int32.  Returns (logits (B, V), new pools).  Slot →
        request mapping, admission, eviction and the block free list are the
        scheduler's problem — this step is pure fixed-shape array math, one
        jit signature per batch-size bucket.  The pools ride the scan carry
        flattened to (L·num_blocks, bs, Hkv·hd) — merging the two leading
        dims is a bitcast — and layer ``li`` appends into and gathers from
        its rows ``li·num_blocks + table`` in place, so no layer's pool is
        ever sliced out or written back and buffer donation keeps one
        pool-sized buffer live.  Dense/moe/vlm only.
        """
        cfg = self.cfg
        if cfg.family not in ("dense", "moe", "vlm"):
            raise NotImplementedError(
                f"decode_step_paged covers dense/moe/vlm, not {cfg.family}")
        x = params["embed"].astype(self.dtype)[token][:, None] * cfg.emb_scale
        pat = len(cfg.window_pattern)
        groups = cfg.num_layers // pat
        gp = _group(params["layers"], groups, pat)
        shape = pools["k"].shape
        num_blocks = shape[1]

        def gbody(carry, inp):
            x, pk, pv = carry
            lp, g = inp
            for j in range(pat):
                with jax.named_scope("weights"):  # this layer's weight slabs
                    pj = jax.tree.map(lambda a, j=j: a[j], lp)
                x, pk, pv = block_decode_paged(
                    cfg, pj, x, pk, pv, (g * pat + j) * num_blocks, table,
                    positions, window=cfg.window_pattern[j],
                )
            return (x, pk, pv), None

        flat = (-1, *shape[2:])
        (x, nk, nv), _ = self._scan(
            gbody, (x, pools["k"].reshape(flat), pools["v"].reshape(flat)),
            (gp, jnp.arange(groups)))
        logits = self._logits(params, x)[:, 0]
        return logits, {"k": nk.reshape(shape), "v": nv.reshape(shape)}
