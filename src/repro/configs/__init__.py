"""Architecture registry: one module per assigned architecture.

``load_all()`` imports every config module exactly once, populating
``base._REGISTRY``.  Import order is deterministic (sorted).
"""
from repro.configs import base as base  # re-export
from repro.configs.base import (  # noqa: F401
    ModelConfig, MoEConfig, SSMConfig, AttentionConfig, FederatedConfig,
    MeshConfig, RunConfig, InputShape, INPUT_SHAPES, TRAIN_4K, PREFILL_32K,
    DECODE_32K, LONG_500K, SINGLE_POD_MESH, MULTI_POD_MESH,
    get_config, all_arch_ids, register, count_params,
)

_ARCH_MODULES = (
    "seamless_m4t_large_v2",
    "llava_next_34b",
    "gemma2_9b",
    "granite_moe_1b_a400m",
    "starcoder2_3b",
    "mamba2_780m",
    "yi_9b",
    "qwen2_0_5b",
    "mixtral_8x7b",
    "zamba2_7b",
    "granite_4_0_h_micro",
    "paper_cnn",
)

_LOADED = False


def load_all() -> None:
    global _LOADED
    if _LOADED:
        return
    _LOADED = True
    import importlib
    for mod in _ARCH_MODULES:
        importlib.import_module(f"repro.configs.{mod}")
