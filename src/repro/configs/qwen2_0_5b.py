"""qwen2-0.5b [dense] — GQA kv=2, QKV bias, tied embeddings.

[arXiv:2407.10671] Qwen2.
"""
from repro.configs.base import AttentionConfig, DENSE, ModelConfig, register

CONFIG = register(ModelConfig(
    arch_id="qwen2-0.5b",
    family=DENSE,
    num_layers=24,
    d_model=896,
    num_heads=14,
    num_kv_heads=2,
    d_ff=4864,
    vocab_size=151936,
    attention=AttentionConfig(rope_theta=1_000_000.0, qkv_bias=True),
    tie_embeddings=True,
    embedding_multiplier=1.0,      # Qwen2 does not scale its embedding
    source="arXiv:2407.10671",
))
