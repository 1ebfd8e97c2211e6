"""qwen3-4b [dense]: 36L, d=2560, 32H (GQA kv=8), d_ff=9728, vocab=151936.

qk_norm, head_dim=128, tied embeddings. [hf:Qwen/Qwen3-4B]
"""
from repro.models.config import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="qwen3_4b", family="dense",
        num_layers=36, d_model=2560, num_heads=32, num_kv_heads=8,
        head_dim=128, d_ff=9728, vocab_size=151936, qk_norm=True,
        tie_embeddings=True, rope_theta=1e6, max_seq_len=32768,
    )


def smoke_config() -> ModelConfig:
    return config().replace(
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
        d_ff=128, vocab_size=256, max_seq_len=128, attn_chunk=16,
    )
