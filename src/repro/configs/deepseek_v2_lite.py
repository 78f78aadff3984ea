"""deepseek-v2-lite [arXiv:2405.04434] — MLA without q compression, 2 shared +
64 routed experts top-6, dropless.

Published config (huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json):
27 layers at hidden 2048, the first a dense FFN of width 10944, then 26 MoE
layers of 64 routed experts of width 1408 and 2 shared ones; softmax gates,
greedy top-6, norm_topk_prob false, routed_scaling_factor 1 (the gates as
they are); MLA with 16
heads, kv_lora_rank 512, qk_nope 128, qk_rope 64, v 128; YaRN RoPE (factor
40, beta_fast 32, beta_slow 1, mscale and mscale_all_dim 0.707, original
4096 positions) at theta 10000; rms_norm_eps 1e-6; vocabulary 102400,
untied head. The router's auxiliary coefficient (aux_loss_alpha) is 0.001.
"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    arch_id="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=10944, vocab_size=102400,
    norm_eps=1e-6,
    n_experts=64, n_shared_experts=2, moe_top_k=6, moe_d_ff=1408,
    first_k_dense=1, capacity_factor=0.0, norm_topk_prob=False,
    router_aux_coef=0.001,
    use_mla=True, q_lora_rank=0, kv_lora_rank=512,
    qk_rope_dim=64, qk_nope_dim=128, v_head_dim=128,
    rope_theta=10000.0, rope_factor=40.0, rope_mscale=0.707,
    rope_mscale_all_dim=0.707,
    n_nodes=8,
    citation="arXiv:2405.04434",
)
