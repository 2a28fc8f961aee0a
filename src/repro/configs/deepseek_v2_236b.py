"""deepseek-v2-236b [moe] — MLA kv_lora=512, 2 shared + 160 routed
experts top-6 (softmax gating, not renormalised, x16), YaRN x40, first
layer dense.  The published group-limited choice (the top 3 of 8 expert
groups first) is not modelled: the top 6 are taken over all experts.
[arXiv:2405.04434; hf]"""
from repro.configs.base import ModelConfig, YarnScaling

CONFIG = ModelConfig(
    name="deepseek-v2-236b", family="moe",
    n_layers=60, d_model=5120, n_heads=128, n_kv_heads=128, head_dim=128,
    d_ff=12288,                       # dense prologue layer FFN
    vocab_size=102400,
    use_mla=True, q_lora_rank=1536, kv_lora_rank=512,
    nope_head_dim=128, rope_head_dim=64, v_head_dim=128,
    n_experts=160, n_shared_experts=2, moe_top_k=6, moe_d_ff=1536,
    moe_gating="softmax_topk", moe_routed_scale=16.0,
    n_dense_layers=1, rope_theta=1e4,
    rope_scaling=YarnScaling(factor=40.0,
                             original_max_position_embeddings=4096,
                             beta_fast=32.0, beta_slow=1.0,
                             mscale=0.707, mscale_all_dim=0.707),
)
