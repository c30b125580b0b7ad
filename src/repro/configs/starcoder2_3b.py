"""starcoder2-3b [dense] — GQA kv=2, RoPE, sliding window 4096,
plain (non-gated) GELU MLP, qkv bias.

[arXiv:2402.19173] StarCoder2.
"""
from repro.configs.base import AttentionConfig, DENSE, ModelConfig, register

CONFIG = register(ModelConfig(
    arch_id="starcoder2-3b",
    family=DENSE,
    num_layers=30,
    d_model=3072,
    num_heads=24,
    num_kv_heads=2,
    d_ff=12288,
    vocab_size=49152,
    attention=AttentionConfig(
        sliding_window=4096,
        rope_theta=999999.4420358813,   # starcoder2-3b rope theta
        qkv_bias=True,
    ),
    mlp_gated=False,
    tie_embeddings=True,
    embedding_multiplier=1.0,      # StarCoder2 does not scale its embedding
    source="arXiv:2402.19173",
))
