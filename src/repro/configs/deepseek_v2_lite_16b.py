"""deepseek-v2-lite-16b [moe] — MLA kv_lora=512, 2 shared + 64 routed top-6.
[arXiv:2405.04434]

From the DeepSeek-V2-Lite model card and ``config.json``: 64 routed + 2
shared experts of width 1408, softmax gating with greedy top-6 whose
scores are not renormalized (``norm_topk_prob`` false,
``routed_scaling_factor`` 1), a dense first layer (d_ff 10944), RMSNorm
eps 1e-6, and YaRN rope scaling (factor 40 over 4,096 original
positions, ``beta_fast`` 32, ``beta_slow`` 1, both mscales 0.707).
"""
from repro.configs.base import MLAConfig, MoEConfig, ModelConfig, YarnConfig

CONFIG = ModelConfig(
    arch_id="deepseek-v2-lite-16b",
    family="moe",
    source="arXiv:2405.04434 (DeepSeek-V2); hf:deepseek-ai/DeepSeek-V2-Lite",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,                   # MLA: per-head latent, kv grouping n/a
    head_dim=128,                    # nominal (nope+rope = 192 qk, 128 v)
    d_ff=10944,                      # dense layers' ffn width
    vocab=102400,
    # first layer dense (unrolled prefix), remaining 26 scanned MoE layers
    prefix_pattern=(("attn", "mlp"),),
    block_pattern=(("attn", "moe"),),
    attention="mla",
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=None,
                  rope_head_dim=64, nope_head_dim=128, v_head_dim=128),
    moe=MoEConfig(num_experts=64, top_k=6, num_shared=2, d_expert=1408,
                  norm_topk_prob=False, scoring_func="softmax",
                  routed_scaling_factor=1.0),
    rope=True,
    rope_theta=10_000.0,
    rope_scaling=YarnConfig(factor=40.0, original_max_position=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                            mscale_all_dim=0.707),
    norm_eps=1e-6,
    subquadratic=False,
    optimizer="adamw",
)
