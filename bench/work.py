"""The work a dense decoder requires, counted from its published widths.

These counts are the yardstick of every roofline and utilization share the
benchmark reports, so they live here and not in the program: they count
the work the model needs, whatever implements it.  A projection counts
``2 M K N`` operations and the bytes of its operands and result, once.  A
step counts its projections at the rows it must compute (the last prompt
token only for the output head in prefill, the live slots only in decode),
attention at its real causal length, the weights read once, the live
cache read once and the new keys and values written once.  Program work
beyond that (padded rows, logits for every prompt position, a whole block
table gathered, a weight rebuilt every step) is not counted, so it shows as
a lower share.

``projections`` is a copy of ``repro.core.cmu.model_gemms`` (the same names
and shapes), kept here so that no change to the program can change it.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

BYTES = 2  # bf16 weights, activations and cache


@dataclass(frozen=True)
class Widths:
    """The sizes of a dense decoder, from its published configuration."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated: bool = True

    @classmethod
    def from_config(cls, c: dict) -> "Widths":
        heads = int(c["num_attention_heads"])
        return cls(
            layers=int(c["num_hidden_layers"]), d_model=int(c["hidden_size"]),
            heads=heads, kv_heads=int(c.get("num_key_value_heads", heads)),
            head_dim=int(c.get("head_dim") or c["hidden_size"] // heads),
            d_ff=int(c["intermediate_size"]), vocab=int(c["vocab_size"]),
            gated=c.get("hidden_act", "silu") in ("silu", "gelu"))

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, o: "Work") -> "Work":
        return Work(self.flops + o.flops, self.bytes + o.bytes)


ZERO = Work(0.0, 0.0)


def projections(w: Widths) -> list[tuple[str, int, int]]:
    """(name, K, N) of each projection of one layer, then the output head."""
    D = w.d_model
    out = [("attn.wq", D, w.q_dim), ("attn.wk", D, w.kv_dim),
           ("attn.wv", D, w.kv_dim), ("attn.wo", w.q_dim, D),
           ("mlp.w1", D, w.d_ff), ("mlp.w2", w.d_ff, D)]
    if w.gated:
        out.append(("mlp.w3", D, w.d_ff))
    out.append(("lm_head", D, w.vocab))
    return out


def gemm(M: int, K: int, N: int) -> Work:
    """One ``(M, K) @ (K, N)`` product: operands read and result written once."""
    return Work(2.0 * M * K * N, float(BYTES * (M * K + K * N + M * N)))


def layer_weight_elems(w: Widths) -> int:
    return sum(K * N for name, K, N in projections(w) if name != "lm_head")


def weight_bytes(w: Widths) -> float:
    """Every weight read once: the layers' projections and the output head
    (the norms are a rounding error and left out)."""
    return float(BYTES * (w.layers * layer_weight_elems(w) + w.d_model * w.vocab))


def kv_bytes_per_token(w: Widths) -> float:
    """Keys and values of one position over all layers."""
    return float(BYTES * 2 * w.layers * w.kv_dim)


def attention_flops(w: Widths, keys: int) -> float:
    """Scores and weighted values of one query over ``keys`` keys, all layers."""
    return 4.0 * w.layers * w.heads * w.head_dim * keys


def prefill(w: Widths, p: int) -> Work:
    """One prompt of ``p`` real tokens: every projection of every layer at
    ``p`` rows, the output head for the last token only, causal attention
    (query ``i`` sees ``i + 1`` keys), the weights read once and the
    prompt's keys and values written once."""
    flops = (2.0 * p * w.layers * layer_weight_elems(w) + 2.0 * w.d_model * w.vocab
             + attention_flops(w, 1) * p * (p + 1) / 2)
    byts = weight_bytes(w) + p * kv_bytes_per_token(w) + BYTES * p * w.d_model
    return Work(flops, byts)


def decode_step(w: Widths, cached: list[int]) -> Work:
    """One decode step of the live slots, ``cached[i]`` positions already
    cached for slot ``i``: a token per slot through every projection and
    the head, attention over ``cached[i] + 1`` keys, the weights read once,
    the live cache read once and one new position written per slot."""
    n = len(cached)
    if n == 0:
        return ZERO
    flops = n * (2.0 * w.layers * layer_weight_elems(w) + 2.0 * w.d_model * w.vocab)
    flops += sum(attention_flops(w, c + 1) for c in cached)
    byts = (weight_bytes(w) + sum(cached) * kv_bytes_per_token(w)
            + n * kv_bytes_per_token(w) + BYTES * n * w.d_model)
    return Work(flops, byts)


def load_peaks(path: Path, device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown device is an error."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def roofline_s(work: Work, peaks: dict) -> float:
    """The least time the chip could take: compute or memory, whichever
    bounds."""
    return max(work.flops / peaks["bf16_flops_per_s"],
               work.bytes / peaks["hbm_bytes_per_s"])
