"""deepseek-v2-lite [moe] — MLA kv_lora=512 without q-LoRA, YaRN x40;
layer 0 dense, then 26 MoE layers of 2 shared + 64 routed experts,
softmax gating top-6 not renormalised.
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434]"""
from repro.configs.base import ModelConfig, YarnScaling

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, head_dim=128,
    d_ff=10944,                       # dense prologue layer FFN
    vocab_size=102400,
    use_mla=True, q_lora_rank=0, kv_lora_rank=512,
    nope_head_dim=128, rope_head_dim=64, v_head_dim=128,
    n_experts=64, n_shared_experts=2, moe_top_k=6, moe_d_ff=1408,
    moe_gating="softmax_topk", moe_routed_scale=1.0,
    n_dense_layers=1, rope_theta=1e4,
    rope_scaling=YarnScaling(factor=40.0,
                             original_max_position_embeddings=4096,
                             beta_fast=32.0, beta_slow=1.0,
                             mscale=0.707, mscale_all_dim=0.707),
)
