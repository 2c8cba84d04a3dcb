"""deepseek-v3 — the paper's own serving backbone (NanoCP evaluates on
DeepSeek-V3 / Kimi-K2).  61L d_model=7168, MLA (128 heads, q_lora=1536,
kv_lora=512, rope=64, nope=128, v=128) with YaRN (factor 40 over 4096),
3 leading dense layers (d_ff=18432) then 58 MoE layers: 256 routed experts
(width 2048) top-8 by a sigmoid group-limited router (8 groups, top-4
groups, score-correction bias, weights renormalised and scaled by 2.5)
plus 1 shared expert.  The multi-token-prediction module is left out.
[arXiv:2412.19437; hf:deepseek-ai/DeepSeek-V3]
"""
from .base import ModelConfig, YaRN

CONFIG = ModelConfig(
    name="deepseek-v3",
    family="moe",
    num_layers=61,
    first_k_dense=3,
    d_model=7168,
    num_heads=128,
    num_kv_heads=128,
    d_ff=18432,
    vocab_size=129280,
    attention="mla",
    rope_theta=10000.0,
    rope_scaling=YaRN(factor=40.0, original_max_position=4096,
                      beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                      mscale_all_dim=1.0),
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    num_experts=256,
    num_experts_per_tok=8,
    num_shared_experts=1,
    moe_d_ff=2048,
    scoring_func="sigmoid",
    score_correction_bias=True,
    n_group=8,
    topk_group=4,
    norm_topk_prob=True,
    routed_scaling_factor=2.5,
    source="arXiv:2412.19437; hf:deepseek-ai/DeepSeek-V3",
)
