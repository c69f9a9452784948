"""Architecture registry + smoke-config reduction.

``get_config(arch_id)`` returns the full published config; ``smoke_config``
shrinks any config to a CPU-runnable variant of the same family (same layer
pattern / mixer kinds / MoE topology, tiny widths) for the per-arch smoke
tests. The FULL configs are only exercised via the dry-run
(ShapeDtypeStruct, no allocation).
"""
from __future__ import annotations

import dataclasses

from repro.configs.base import (
    SHAPES,
    AttentionRuntime,
    CPQCfg,
    MLACfg,
    MambaCfg,
    ModelConfig,
    MoECfg,
    RetrievalCfg,
    ServingCfg,
    ShapeCfg,
    XLSTMCfg,
    YarnCfg,
    cell_supported,
)

from repro.configs import (  # noqa: E402
    deepseek_v2_lite_16b,
    deepseek_moe_16b,
    llama_3_2_vision_11b,
    musicgen_large,
    xlstm_125m,
    qwen1_5_0_5b,
    gemma_2b,
    phi4_mini_3_8b,
    qwen3_4b,
    jamba_1_5_large_398b,
    opt_6_7b,
)

ARCHS: dict[str, ModelConfig] = {
    m.CONFIG.name: m.CONFIG
    for m in (
        deepseek_v2_lite_16b,
        deepseek_moe_16b,
        llama_3_2_vision_11b,
        musicgen_large,
        xlstm_125m,
        qwen1_5_0_5b,
        gemma_2b,
        phi4_mini_3_8b,
        qwen3_4b,
        jamba_1_5_large_398b,
        opt_6_7b,  # paper's eval model (not part of the 10-arch assignment)
    )
}

ASSIGNED = tuple(n for n in ARCHS if n != "opt-6.7b")


def get_config(name: str) -> ModelConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(ARCHS)}")
    return ARCHS[name]


def smoke_config(cfg: ModelConfig) -> ModelConfig:
    """Reduced same-family config: tiny widths, 1 block, small vocab."""
    kw: dict = dict(
        name=cfg.name + "-smoke",
        d_model=64,
        num_heads=4,
        num_kv_heads=min(cfg.num_kv_heads, 4) if cfg.num_kv_heads > 1 else 1,
        head_dim=16,
        d_ff=0 if cfg.d_ff == 0 else 96,
        vocab_size=256,
        num_blocks=1,
        num_patch_tokens=16 if cfg.num_patch_tokens else 0,
    )
    if cfg.moe is not None:
        kw["moe"] = dataclasses.replace(
            cfg.moe,
            num_experts=8,
            num_shared=min(cfg.moe.num_shared, 1),
            top_k=2,
            d_ff_expert=32,
            capacity_factor=2.0,
        )
        kw["experts_held"] = 0
    if cfg.mla is not None:
        kw["mla"] = MLACfg(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)
    if cfg.mamba is not None:
        kw["mamba"] = MambaCfg(d_state=8, d_conv=4, expand=2)
    if cfg.xlstm is not None:
        kw["xlstm"] = XLSTMCfg(proj_factor=2.0, conv_kernel=4, chunk=16)
    return dataclasses.replace(cfg, **kw)


__all__ = [
    "ARCHS",
    "ASSIGNED",
    "SHAPES",
    "AttentionRuntime",
    "CPQCfg",
    "MLACfg",
    "MambaCfg",
    "ModelConfig",
    "MoECfg",
    "RetrievalCfg",
    "ServingCfg",
    "ShapeCfg",
    "XLSTMCfg",
    "YarnCfg",
    "cell_supported",
    "get_config",
    "smoke_config",
]
