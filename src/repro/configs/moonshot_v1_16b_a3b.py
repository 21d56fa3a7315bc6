"""moonshot-v1-16b-a3b [moe] — Moonlight-16B-A3B (DeepSeek-V3 architecture).

27L d_model=2048 16H, latent attention (kv_lora_rank 512, qk 128 + rope 64,
v 128, q projected directly), layer 0 dense SwiGLU d_ff=11264, layers 1-26
MoE: 64 routed experts of 1408 top-6, sigmoid scores with a selection-only
bias, top-6 normalised and scaled by 2.446, plus 2 shared experts;
vocab=163840 untied, rope_theta 50000, rms eps 1e-5
[hf:moonshotai/Moonlight-16B-A3B].
"""
from repro.models.config import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonshot-v1-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=192,
    d_ff=11264,
    vocab_size=163840,
    rope_theta=50_000.0,
    norm_eps=1e-5,
    layer_pattern=("mla",),
    first_k_dense=1,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                  v_head_dim=128),
    moe=MoEConfig(num_experts=64, top_k=6, d_expert=1408, scoring="sigmoid",
                  routed_scale=2.446, n_shared=2),
)

SMOKE = ModelConfig(
    name="moonshot-v1-16b-a3b-smoke",
    family="moe",
    num_layers=3,
    d_model=64,
    num_heads=4,
    num_kv_heads=4,
    head_dim=24,
    d_ff=96,
    vocab_size=128,
    rope_theta=50_000.0,
    norm_eps=1e-5,
    layer_pattern=("mla",),
    first_k_dense=1,
    mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  v_head_dim=16),
    moe=MoEConfig(num_experts=8, top_k=2, d_expert=32, capacity_factor=8.0,
                  scoring="sigmoid", routed_scale=2.446, n_shared=2),
    attn_chunk=16,
    loss_chunk=16,
)
