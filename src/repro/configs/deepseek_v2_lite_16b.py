"""DeepSeek-V2-Lite (15.7B total / 2.4B active) [arXiv:2405.04434; hf].

27L d_model=2048 16H, MLA (kv_lora=512, nope=128, rope=64, v=128),
MoE: 2 shared + 64 routed top-6, expert d_ff=1408; first layer dense MLP.
Gating: softmax over all 64, greedy top-6, no renormalisation
(``norm_topk_prob: false``), ``routed_scaling_factor`` 1. Rope: YaRN, factor
40 over 4096 original positions (``rope_scaling`` of the published config).
Note: the assignment header lists both "MoE 64e top-6" and "160 routed"; 160
is the DeepSeek-V2 (236B) value — V2-Lite uses 64 routed, which we follow.
MLA is implemented with the absorbed decode path, which is exactly the
paper's T1 matrix decomposition applied to a learned 512-d latent cache.
"""
from repro.configs.base import MLACfg, ModelConfig, MoECfg, YarnCfg

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=128,  # v head dim; qk dims come from MLACfg
    d_ff=10944,    # dense first layer (V2-Lite value)
    vocab_size=102400,
    prefix_pattern=(("mla", "dense"),),
    block_pattern=(("mla", "moe"),),
    num_blocks=26,
    mlp_act="swiglu",
    norm="rmsnorm",
    rope_theta=10000.0,
    rope_scaling=YarnCfg(factor=40.0, original_max_position_embeddings=4096,
                         beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                         mscale_all_dim=0.707),
    moe=MoECfg(num_experts=64, num_shared=2, top_k=6, d_ff_expert=1408,
               norm_topk=False),
    mla=MLACfg(kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128),
)
