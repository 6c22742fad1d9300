"""DeepSeek-V2-Lite [arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite].

27L, d_model=2048, 16 heads (MLA: kv_lora=512, no query compression,
rope_hd=64, nope_hd=128, v_hd=128; YaRN factor 40 over 4096 positions),
d_ff(dense)=10944, MoE: 64 routed experts top-6 (softmax gates, not
renormalised) + 2 shared, expert hidden 1408, first layer dense, vocab 102400.
"""
from repro.configs.base import ModelConfig, register

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    arch_type="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=10944,            # dense-layer FFN hidden (first_k_dense layers)
    moe_d_ff=1408,
    vocab_size=102400,
    mla=True,
    mla_kv_lora_rank=512,
    mla_q_lora_rank=0,     # q_lora_rank null: a plain query projection
    mla_rope_head_dim=64,
    mla_nope_head_dim=128,
    mla_v_head_dim=128,
    rope_theta=10000.0,
    rope_yarn_factor=40.0,
    rope_yarn_original_max=4096,
    rope_yarn_beta_fast=32.0,
    rope_yarn_beta_slow=1.0,
    rope_yarn_mscale=0.707,
    rope_yarn_mscale_all_dim=0.707,
    moe=True,
    num_experts=64,
    num_experts_per_tok=6,
    num_shared_experts=2,
    first_k_dense=1,
    moe_norm_topk_prob=False,
    moe_routed_scaling=1.0,
    ffn_activation="swiglu",
    norm_eps=1e-6,
)


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite-smoke",
        arch_type="moe",
        num_layers=3,
        d_model=64,
        num_heads=4,
        num_kv_heads=4,
        d_ff=128,
        moe_d_ff=32,
        vocab_size=256,
        mla=True,
        mla_kv_lora_rank=32,
        mla_q_lora_rank=0,
        mla_rope_head_dim=16,
        mla_nope_head_dim=16,
        mla_v_head_dim=16,
        rope_yarn_factor=40.0,
        rope_yarn_original_max=64,
        rope_yarn_mscale=0.707,
        rope_yarn_mscale_all_dim=0.707,
        moe=True,
        num_experts=16,
        num_experts_per_tok=3,
        num_shared_experts=2,
        first_k_dense=1,
        moe_norm_topk_prob=False,
        moe_experts_held=4,
        moe_expert_offset=4,
        ffn_activation="swiglu",
    )


register(CONFIG, smoke_config)
