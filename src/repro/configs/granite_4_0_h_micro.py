"""granite-4.0-h-micro [hybrid] — Mamba-2 layers with one GQA attention
layer in ten, a gated MLP after every mixer, µP-style multipliers.

[hf:ibm-granite/granite-4.0-h-micro] config.json: 40 layers, attention at
indices 5, 15, 25, 35 (the period below); hidden 2048; 32 query heads and
8 KV heads of 64, no position embedding; Mamba-2 with 64 heads of 64,
d_state 128, one group, conv 4, chunk 256; ``shared_intermediate_size``
8192, silu-gated, after the mixer of every layer; embedding multiplier
12, residual multiplier 0.22, attention multiplier 1/64, logits divided
by 8; RMSNorm eps 1e-5; tied vocabulary of 100,352.
"""
from repro.configs.base import (
    ATTN_GLOBAL, AttentionConfig, HYBRID, MAMBA, ModelConfig, SSMConfig,
    register,
)

CONFIG = register(ModelConfig(
    arch_id="granite-4.0-h-micro",
    family=HYBRID,
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab_size=100352,
    attention=AttentionConfig(
        pattern=(MAMBA,) * 5 + (ATTN_GLOBAL,) + (MAMBA,) * 4,
        # attention_multiplier 0.015625 = 1 / sqrt(4096)
        query_pre_attn_scalar=4096.0,
        position_embedding="nope",
    ),
    ssm=SSMConfig(d_state=128, d_conv=4, expand=2, head_dim=64,
                  n_groups=1, chunk_size=256),
    norm_eps=1e-5,
    tie_embeddings=True,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    mlp_act="silu",
    mlp_packed=True,
    mamba_mlp=True,
    source="hf:ibm-granite/granite-4.0-h-micro",
))
