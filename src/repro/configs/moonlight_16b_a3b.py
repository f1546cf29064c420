"""Moonlight-16B-A3B [moe] — DeepSeek-V3 blocks
(hf:moonshotai/Moonlight-16B-A3B, ``model_type: deepseek_v3``).
27L: layer 0 dense (SwiGLU 11264), layers 1–26 MoE with 64 routed
experts of width 1408, top-6 by sigmoid score + correction bias,
normalised, × 2.446, plus 2 shared experts (one SwiGLU of 2816).
d_model 2048, multi-head latent attention with 16 heads, no query
latent, kv_lora_rank 512, rope-free q/k and v head dims 128, one
64-wide rotary key, rope θ 50,000; untied head, vocab 163840."""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, head_dim=128,
    d_ff=11264, vocab=163840, rope_theta=50000.0, norm_eps=1e-5,
    n_dense_layers=1, moe_experts=64, moe_topk=6, moe_ff=1408,
    shared_ff=2816, moe_router="sigmoid", moe_routed_scale=2.446,
    kv_lora_rank=512, qk_rope_dim=64,
)

# the same block at tiny widths: one dense layer, two MoE layers, the
# capacity path dropless too, so training and serving route alike
SMOKE = ArchConfig(
    name="moonlight-16b-a3b-smoke", family="moe",
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
    d_ff=128, vocab=512, rope_theta=50000.0, norm_eps=1e-5,
    n_dense_layers=1, moe_experts=8, moe_topk=3, moe_ff=32,
    shared_ff=64, moe_router="sigmoid", moe_routed_scale=2.446,
    kv_lora_rank=32, qk_rope_dim=8, capacity_factor=-1.0,
    param_dtype="float32", compute_dtype="float32",
)
