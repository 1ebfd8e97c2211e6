"""Random weights of a dense decoder, made on the device from the seed.

The benchmark makes the weights itself, so that the plain reference takes
nothing the program made.  The tree follows the program's parameter
layout (the harness checks it against the program's own shapes before
serving):

  embed (V_pad, D); final_norm.scale (D,); lm_head (D, V_pad) when untied;
  layers.{ln1,ln2}.scale (L, D);
  layers.attn.{wq (L, D, Hq*hd), wk/wv (L, D, Hkv*hd), wo (L, Hq*hd, D)};
  layers.attn.{q_norm,k_norm} (L, hd) with per-head query/key norms;
  layers.mlp.{w1 gate, w3 up (L, D, F), w2 down (L, F, D)}.

A norm's ``scale`` multiplies as ``1 + scale``.  The vocabulary is padded
to a multiple of 256; the padding rows are zero, so no padding id ever
wins an argmax.  Draws: projections N(0, 1/K); norm offsets N(0, 0.1^2);
the embedding N(0, 1 / (scale_emb^2 D)).  The blocks' output projections
(``wo``, ``w2``) are drawn 1 / residual_scale larger (MiniCPM's
``scale_depth / sqrt(layers)``; 1 for Qwen3).  Both keep every
configuration's residual stream made of its blocks' outputs, of about
unit size each, over an embedding of about 1/sqrt(D): with the embedding
dominant, a tied head ranks the input token itself first by a wide
margin, every greedy token repeats the prompt's last one, and no
rounding, however coarse, changes a token.  Qwen3's weights are the plain
draws either way.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NORM_SIGMA = 0.1


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 256) * 256


def shapes(c: dict, qk_norm: bool, tied: bool) -> dict:
    """The tree of shapes for published configuration ``c``."""
    L, D = int(c["num_hidden_layers"]), int(c["hidden_size"])
    H = int(c["num_attention_heads"])
    Hkv = int(c.get("num_key_value_heads", H))
    hd = int(c.get("head_dim") or D // H)
    F, Vp = int(c["intermediate_size"]), padded_vocab(int(c["vocab_size"]))
    attn = {"wq": (L, D, H * hd), "wk": (L, D, Hkv * hd),
            "wv": (L, D, Hkv * hd), "wo": (L, H * hd, D)}
    if qk_norm:
        attn |= {"q_norm": (L, hd), "k_norm": (L, hd)}
    tree = {"embed": (Vp, D), "final_norm": {"scale": (D,)},
            "layers": {"ln1": {"scale": (L, D)}, "ln2": {"scale": (L, D)},
                       "attn": attn,
                       "mlp": {"w1": (L, D, F), "w2": (L, F, D), "w3": (L, D, F)}}}
    if not tied:
        tree["lm_head"] = (D, Vp)
    return tree


def residual_scale(c: dict) -> float:
    """What each block's output is multiplied by on the residual stream."""
    if "scale_depth" in c:
        return float(c["scale_depth"]) / math.sqrt(float(c["num_hidden_layers"]))
    return 1.0


def make(c: dict, seed: int, *, qk_norm: bool, tied: bool):
    """All weights as bf16 device arrays, from one jitted program."""
    tree = shapes(c, qk_norm, tied)
    vocab, D = int(c["vocab_size"]), int(c["hidden_size"])
    emb_sigma = 1.0 / (float(c.get("scale_emb", 1.0)) * math.sqrt(D))
    out_scale = 1.0 / residual_scale(c)
    paths = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))[0]

    def build(key):
        leaves = []
        for i, (path, shape) in enumerate(paths):
            name = jax.tree_util.keystr(path)
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, shape, jnp.float32)
            if name == "['embed']":
                a = z * emb_sigma
                a = jnp.where(jnp.arange(shape[0])[:, None] < vocab, a, 0.0)
            elif "norm" in name or "ln" in name:
                a = z * NORM_SIGMA
            else:
                a = z / math.sqrt(shape[-2])
                if out_scale != 1.0 and name.endswith(("['wo']", "['w2']")):
                    a = a * out_scale
            leaves.append(a.astype(jnp.bfloat16))
        treedef = jax.tree_util.tree_structure(
            tree, is_leaf=lambda x: isinstance(x, tuple))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(jax.random.key(seed % (2 ** 32)))
