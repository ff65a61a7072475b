"""deepseek-v2-lite-16b [moe] — arXiv:2405.04434; the release's
config.json (huggingface.co/deepseek-ai/DeepSeek-V2-Lite).

27L d_model=2048 16H MLA (no q compression, kv_lora_rank=512,
qk_nope=128, qk_rope=64, v=128) with YaRN rotary scaling (factor 40,
original 4096, beta 32/1, mscale = mscale_all_dim = 0.707), RMSNorm eps
1e-6, vocab=102400, untied head. Layer 0 is a dense SwiGLU of width
10944 (first_k_dense_replace=1); layers 1-26 are MoE: 64 routed experts
of width 1408, softmax gate, greedy top-6 NOT renormalised
(norm_topk_prob false, routed_scaling_factor 1), plus 2 shared experts
(one SwiGLU of width 2816). LoRA attaches where a PEFT user trains on
this model: q_proj, kv_a_proj_with_mqa, kv_b_proj, o_proj.
"""
from .base import LoRAConfig, MLAConfig, ModelConfig, MoEConfig, RopeScaling

MLA_TARGETS = ("q", "kv_a", "kv_b", "o")


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite-16b",
        family="moe",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        d_ff=1408,
        vocab_size=102400,
        rmsnorm_eps=1e-6,
        n_dense_layers=1,
        dense_d_ff=10944,
        mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                      qk_rope_head_dim=64, v_head_dim=128, q_lora_rank=0),
        moe=MoEConfig(n_experts=64, top_k=6, d_ff_expert=1408,
                      n_shared_experts=2, norm_topk=False,
                      routed_scale=1.0),
        rope_scaling=RopeScaling(factor=40.0, original_max_position=4096,
                                 beta_fast=32.0, beta_slow=1.0,
                                 mscale=0.707, mscale_all_dim=0.707),
        lora=LoRAConfig(targets=MLA_TARGETS),
        source="arXiv:2405.04434",
    )


def reduced() -> ModelConfig:
    """One leading dense layer and one MoE layer at smoke widths, with
    the release's mechanisms: YaRN, no renormalisation, the MLA LoRA
    targets."""
    return ModelConfig(
        name="deepseek-v2-lite-16b-smoke",
        family="moe",
        n_layers=2,
        d_model=128,
        n_heads=4,
        n_kv_heads=4,
        d_ff=64,
        vocab_size=512,
        rmsnorm_eps=1e-6,
        n_dense_layers=1,
        dense_d_ff=192,
        mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16,
                      qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=0),
        moe=MoEConfig(n_experts=4, top_k=2, d_ff_expert=64,
                      n_shared_experts=1, norm_topk=False),
        rope_scaling=RopeScaling(factor=40.0, original_max_position=64,
                                 beta_fast=32.0, beta_slow=1.0,
                                 mscale=0.707, mscale_all_dim=0.707),
        lora=LoRAConfig(targets=MLA_TARGETS),
        source="smoke",
    )
