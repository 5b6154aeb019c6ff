"""moonlight-16b-a3b [moe]: 27L d=2048, latent attention (16H, kv_lora 512,
nope 128 + rope 64, v 128), one leading dense layer of 11264, then 64 routed
experts of 1408 (top-6, sigmoid scores + correction bias, gates normalized
x 2.446) and 2 shared experts; untied vocab 163840.
[hf:moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3]

``n_kv_heads=1``: the latent cache is one 576-wide "KV head" (the 512
latent and the 64-wide RoPE key) that all 16 query heads read, so decode
attention is MQA over it (`models/layers.py::mla_attention`).
"""
from .base import ModelConfig, register

CONFIG = register(ModelConfig(
    name="moonlight-16b-a3b",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=1, d_ff=11_264,
    vocab_size=163_840,
    mixer_pattern=("mla",), ffn_pattern=("moe",), first_k_dense=1,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    n_experts=64, top_k=6, moe_d_ff=1408, n_shared_experts=2,
    router_scoring="sigmoid", routed_scaling_factor=2.446,
    activation="silu", glu=True, norm="rmsnorm", norm_eps=1e-5,
    pos_emb="rope", rope_theta=5e4, tie_embeddings=False, max_seq_len=8192,
    family="moe", supports_long_context=False,
))
