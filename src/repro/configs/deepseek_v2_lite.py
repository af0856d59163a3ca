"""DeepSeek-V2-Lite — MLA with YaRN, 64 routed experts top-6, 2 shared.

[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite config.json] 27 layers,
d_model 2048, 16 heads, vocab 102400. MLA without q compression: kv_lora
512, qk_nope 128, qk_rope 64, v_head 128; RoPE theta 10000 with YaRN
(factor 40 over 4096 original positions, beta_fast 32, beta_slow 1, mscale
and mscale_all_dim 0.707). First layer dense (SwiGLU 10944); 26 MoE layers
of 64 routed experts (width 1408, softmax routing, greedy top-6, weights
not renormalised, scaling 1) and 2 shared experts.

Registered whole, the way the checkpoint describes it: every expert held
by the one dropless expert layer, the full vocabulary. One chip's share of
an expert-parallel deployment sets ``experts_held`` and
``experts_held_from`` to the experts it holds (the router keeps all
``num_experts`` outputs and its top-k), and may cut ``vocab_size`` and
``num_layers`` the same way.
RoPE channels follow the repository's halves convention; a published
checkpoint's interleaved rope columns map onto it by a fixed permutation.
"""
from repro.configs.base import ArchConfig, register


@register("deepseek-v2-lite")
def deepseek_v2_lite() -> ArchConfig:
    return ArchConfig(
        name="deepseek-v2-lite",
        family="moe",
        source="hf:deepseek-ai/DeepSeek-V2-Lite (arXiv:2405.04434)",
        num_layers=27,
        d_model=2048,
        vocab_size=102400,
        attention="mla",
        num_heads=16,
        num_kv_heads=16,
        head_dim=128,
        rope_theta=10_000.0,
        yarn_factor=40.0,
        yarn_original_max_pos=4096,
        yarn_beta_fast=32.0,
        yarn_beta_slow=1.0,
        yarn_mscale=0.707,
        yarn_mscale_all_dim=0.707,
        q_lora_rank=0,
        kv_lora_rank=512,
        qk_nope_dim=128,
        qk_rope_dim=64,
        v_head_dim=128,
        d_ff=1408,
        num_experts=64,
        num_experts_per_tok=6,
        num_shared_experts=2,
        moe_d_ff=1408,
        experts_held=64,
        experts_held_from=0,
        first_dense_layers=1,
        dense_d_ff=10944,
        norm_topk_prob=False,
        routed_scaling_factor=1.0,
        norm_eps=1e-6,
        supports_long_context=True,
        remat="full",
    )

