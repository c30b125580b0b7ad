"""granite-moe-1b-a400m [moe] — 32 experts, top-8, tiny experts (d_ff=512).

[hf:ibm-granite/granite-3.0-1b-a400m-base]
"""
import math

from repro.configs.base import AttentionConfig, MOE, MoEConfig, ModelConfig, register

CONFIG = register(ModelConfig(
    arch_id="granite-moe-1b-a400m",
    family=MOE,
    num_layers=24,
    d_model=1024,
    num_heads=16,
    num_kv_heads=8,
    d_ff=512,                   # == expert_d_ff (every FFN is MoE)
    vocab_size=49155,
    attention=AttentionConfig(rope_theta=10000.0),
    moe=MoEConfig(num_experts=32, top_k=8, expert_d_ff=512,
                  capacity_factor=1.25, group_size=4096),
    tie_embeddings=True,
    # sqrt(d_model), as the tied embedding was scaled before the scale
    # became a stated value; the published multiplier is not in the
    # sources cited here
    embedding_multiplier=math.sqrt(1024),
    source="hf:ibm-granite/granite-3.0-1b-a400m-base",
))
