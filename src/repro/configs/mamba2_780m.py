"""mamba2-780m [ssm] — pure Mamba2 (SSD), attention-free.

[arXiv:2405.21060] Transformers are SSMs (state-space duality).
d_model=1536, expand=2 -> d_inner=3072, head_dim P=64 -> 48 SSD heads,
d_state N=128, 48 layers, vocab 50280 (gpt-neox tokenizer, padded).
"""
from repro.configs.base import ModelConfig, SSM, SSMConfig, register

CONFIG = register(ModelConfig(
    arch_id="mamba2-780m",
    family=SSM,
    num_layers=48,
    d_model=1536,
    num_heads=1,              # unused by SSD blocks
    num_kv_heads=1,
    d_ff=0,                   # attention-free, no separate MLP
    vocab_size=50280,
    ssm=SSMConfig(d_state=128, d_conv=4, expand=2, head_dim=64,
                  n_groups=1, chunk_size=128),
    tie_embeddings=True,
    embedding_multiplier=1.0,      # Mamba-2 does not scale its embedding
    source="arXiv:2405.21060",
))
