"""gemma2-9b [dense] — alternating local(4096-window)/global attention,
attn logit softcap 50, final logit softcap 30, head_dim=256, GeGLU.

[arXiv:2408.00118] Gemma 2.
"""
import math

from repro.configs.base import (
    ATTN_GLOBAL, ATTN_LOCAL, AttentionConfig, DENSE, ModelConfig, register,
)

CONFIG = register(ModelConfig(
    arch_id="gemma2-9b",
    family=DENSE,
    num_layers=42,
    d_model=3584,
    num_heads=16,
    num_kv_heads=8,
    head_dim=256,
    d_ff=14336,
    vocab_size=256000,
    attention=AttentionConfig(
        sliding_window=4096,
        pattern=(ATTN_LOCAL, ATTN_GLOBAL),
        rope_theta=10000.0,
        attn_logit_softcap=50.0,
        query_pre_attn_scalar=256.0,   # gemma2 scales q by 1/sqrt(256)
    ),
    final_logit_softcap=30.0,
    use_post_norms=True,
    tie_embeddings=True,
    embedding_multiplier=math.sqrt(3584),    # gemma scales by sqrt(d_model)
    source="arXiv:2408.00118",
))
