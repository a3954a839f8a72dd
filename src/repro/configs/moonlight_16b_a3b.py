"""Moonlight-16B-A3B [hf:moonshotai/Moonlight-16B-A3B, config.json,
model_type deepseek_v3]: 27 layers at d_model 2048, the first a dense
SwiGLU of width 11264 (first_k_dense_replace 1), the other 26 MoE with
64 routed SwiGLU experts of width 1408, 6 a token, plus 2 shared experts;
sigmoid scoring with the noaux_tc correction bias (n_group = topk_group
= 1), normalised top-k weights scaled by 2.446.  Attention is MLA: 16
heads, a plain q projection (q_lora_rank null) to 128 nope + 64 rope
dims, kv_lora_rank 512, v_head_dim 128; rope_theta 50,000, untied head,
vocabulary 163,840, 8,192 positions."""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="moe",
    num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=11_264, moe_d_ff=1408, vocab_size=163_840, mlp_kind="swiglu",
    rope_theta=50_000.0, norm_eps=1e-5,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    num_experts=64, top_k=6, n_shared_experts=2, first_k_dense_replace=1,
    router_scoring="sigmoid", routed_scaling_factor=2.446,
    param_dtype="bfloat16",
)
