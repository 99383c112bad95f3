"""Moonlight-16B-A3B (DeepSeek-V3 layout): latent attention, one leading
dense layer, then 26 layers of 64 routed experts (top-6, sigmoid scores
with a selection bias, normalised gates scaled by 2.446) and 2 shared.

[hf:moonshotai/Moonlight-16B-A3B/blob/main/config.json; hf]
"""
from repro.configs.base import ArchConfig, register

CONFIG = register(ArchConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=1408,                   # moe_intermediate_size, per routed expert
    vocab_size=163840,
    num_experts=64,
    experts_per_token=6,
    moe_every=1,
    shared_experts=2,            # one SwiGLU of 2 x 1408
    router_scoring="sigmoid",
    routed_scaling_factor=2.446,
    first_k_dense_replace=1,
    dense_d_ff=11264,            # intermediate_size
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    act="swiglu",
    norm="rmsnorm",
    norm_eps=1e-5,
    tie_embeddings=False,
    rope_theta=50_000.0,
    layer_group=1,
    remat="full",
    source="[hf:moonshotai/Moonlight-16B-A3B/blob/main/config.json; hf]",
))
