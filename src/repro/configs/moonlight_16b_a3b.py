"""Moonlight-16B-A3B [arXiv:2502.16982; config
https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json,
model_type deepseek_v3, layer equations of arXiv:2412.19437 §2.1].

27L d_model=2048, layer 0 dense (d_ff=11264), then 26 MoE layers: 64 routed
experts of width 1408, top-6 by sigmoid score plus a correction bias
(noaux_tc, one group), chosen scores normalised and scaled by 2.446, and 2
shared experts. MLA with 16 heads, no query compression, kv_lora_rank 512,
qk_nope 128 + qk_rope 64, v 128. rope_theta 50000, rms_norm_eps 1e-5,
vocab 163840, untied. Routed experts drop no token.
"""
from repro.configs.base import DENSE, MLA, MOE, LayerSpec, ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    first_dense_layers=1,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=192,
    d_ff=11264,
    vocab_size=163840,
    block_pattern=(LayerSpec(mixer=MLA, ffn=MOE),),
    num_experts=64,
    num_shared_experts=2,
    top_k=6,
    moe_d_ff=1408,
    capacity_factor=0.0,
    router_score="sigmoid",
    routed_scaling=2.446,
    # DeepSeek-V3's values (arXiv:2412.19437 §4.2); Moonlight's own are not
    # published
    bias_update_rate=0.001,
    router_aux_coef=0.0001,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=50_000.0,
    norm_eps=1e-5,
    tie_embeddings=False,
    max_position=8192,
    source="arXiv:2502.16982",
)
