"""Model configuration covering all ten assigned architecture families."""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass


@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"  # dense | moe | ssm | hybrid | encdec | vlm
    num_layers: int = 2
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    d_ff: int = 1024
    vocab_size: int = 1024
    head_dim: int = 0          # 0 -> d_model // num_heads
    max_seq_len: int = 4096

    # attention variants
    qkv_bias: bool = False          # qwen1.5
    qk_norm: bool = False           # qwen3, gemma3
    window_pattern: tuple[int, ...] = (0,)  # per-layer sliding windows, cycled;
                                            # 0 = full/global. gemma3: (1024,)*5+(0,)
    rope_theta: float = 10_000.0
    norm: str = "rmsnorm"           # rmsnorm | layernorm
    norm_eps: float = 1e-6
    activation: str = "silu"        # silu (swiglu) | gelu (geglu) | gelu_mlp
    tie_embeddings: bool = False
    logit_softcap: float = 0.0

    # residual-stream scaling (minicpm / gemma)
    emb_scale: float = 1.0          # multiply token embeddings
    residual_scale: float = 1.0     # multiply block outputs (minicpm depth-scale)
    logit_divisor: float = 1.0      # divide the f32 logits (minicpm: d_model / dim_model_base)

    # MoE
    num_experts: int = 0
    top_k: int = 0
    expert_d_ff: int = 0            # per-expert hidden dim
    moe_dense_ff: int = 0           # arctic: parallel dense-FFN residual branch
    capacity_factor: float = 1.25

    # SSM / hybrid
    ssm_state: int = 0              # mamba2 head state size
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv: int = 4
    attn_every: int = 0             # zamba2: shared attn block after every k-th layer
    rwkv_head_size: int = 64
    rwkv_decay_lora: int = 64       # Finch data-dependent decay low-rank size

    # encoder-decoder (whisper)
    num_enc_layers: int = 0
    enc_seq_len: int = 1500         # audio frames from the (stubbed) conv frontend

    # VLM (paligemma)
    vision_tokens: int = 0          # prefix patch embeddings from stubbed SigLIP
    vision_embed_dim: int = 0       # SigLIP output dim (0 -> d_model)

    # numerics
    dtype: str = "bfloat16"
    param_dtype: str = "float32"

    # kernels / dispatch
    use_pallas: bool = False        # pallas kernels (interpret on CPU); XLA path off
    attn_chunk: int = 128           # query-chunked attention block (per seq shard)
    attn_unroll: bool = False       # unroll the chunk scan (exact HLO cost probes)
    attn_pallas: bool = False       # flash/paged attention via the planned
                                    # flex kernel family (forward/serve only)
    ssm_pallas: bool = False        # chunked-scan / decode-step via the planned
                                    # flex scan kernel family (ssm + hybrid)

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a multiple of 256 so embed/lm_head shard evenly
        on the 16-way tensor axis (MaxText-style; labels always < vocab_size)."""
        return -(-self.vocab_size // 256) * 256

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def ssm_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.ssm_inner // self.ssm_head_dim

    @property
    def rwkv_heads(self) -> int:
        return self.d_model // self.rwkv_head_size

    def window_for_layer(self, i: int) -> int:
        return self.window_pattern[i % len(self.window_pattern)]

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # ---- parameter counting (for MODEL_FLOPS / roofline) -------------------
    def param_count(self) -> tuple[int, int]:
        """(total_params, active_params_per_token)."""
        D, F, V, L = self.d_model, self.d_ff, self.vocab_size, self.num_layers
        emb = V * D * (1 if self.tie_embeddings else 2)

        def attn_p() -> int:
            p = D * self.q_dim + 2 * D * self.kv_dim + self.q_dim * D
            if self.qkv_bias:
                p += self.q_dim + 2 * self.kv_dim
            return p

        def mlp_p(ff: int) -> int:
            mats = 3 if self.activation in ("silu", "gelu") else 2
            return mats * D * ff

        total = active = 0
        if self.family in ("dense", "vlm"):
            per = attn_p() + mlp_p(F) + 2 * D
            total = active = L * per
        elif self.family == "moe":
            e_ff = self.expert_d_ff or F
            per_shared = attn_p() + 2 * D + D * self.num_experts
            per_shared += mlp_p(self.moe_dense_ff) if self.moe_dense_ff else 0
            total = L * (per_shared + self.num_experts * mlp_p(e_ff))
            active = L * (per_shared + self.top_k * mlp_p(e_ff))
        elif self.family == "ssm":  # rwkv6
            H = self.rwkv_head_size
            per = 4 * D * D + D * D  # r,k,v,g,out
            per += 2 * self.rwkv_decay_lora * D + D * H  # decay lora + u
            per += 2 * D * F // 2 + D * D  # channel mix (k: D->F', v: F'->D, r: D->D)
            total = active = L * per
        elif self.family == "hybrid":  # zamba2: mamba layers + one shared attn block
            di = self.ssm_inner
            per_mamba = D * (2 * di + 2 * self.ssm_state + self.ssm_heads) + di * D
            per_mamba += self.ssm_conv * (di + 2 * self.ssm_state) + 2 * self.ssm_heads
            shared = attn_p() + mlp_p(F) + 2 * D
            total = active = L * per_mamba + shared
        elif self.family == "encdec":
            enc = self.num_enc_layers * (attn_p() + mlp_p(F) + 2 * D)
            dec = L * (2 * attn_p() + mlp_p(F) + 3 * D)
            total = active = enc + dec
        total += emb
        active += emb
        return total, active
