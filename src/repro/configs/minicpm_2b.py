"""minicpm-2b [dense]: 40L, d=2304, 36H (kv=36), d_ff=5760, vocab=122753.

WSD schedule (optim feature); mup-style embed scale 12, depth-scaled
residuals (1.4/sqrt(L)) and a tied head over hidden_size / dim_model_base
(2304 / 256 = 9); rms_norm_eps 1e-5. [hf:openbmb/MiniCPM-2B-sft-bf16,
arXiv:2404.06395]
"""
import math

from repro.models.config import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="minicpm_2b", family="dense",
        num_layers=40, d_model=2304, num_heads=36, num_kv_heads=36,
        d_ff=5760, vocab_size=122753, tie_embeddings=True,
        emb_scale=12.0, residual_scale=1.4 / math.sqrt(40),
        logit_divisor=2304 / 256, norm_eps=1e-5, max_seq_len=32768,
    )


def smoke_config() -> ModelConfig:
    return config().replace(
        num_layers=2, d_model=72, num_heads=4, num_kv_heads=4, d_ff=144,
        vocab_size=256, residual_scale=1.4 / math.sqrt(2),
        max_seq_len=128, attn_chunk=16,
    )
