"""Moonlight-16B-A3B — DeepSeek-V3 architecture: MLA and sigmoid-routed
MoE with shared experts [hf:moonshotai/Moonlight-16B-A3B].

27L, d_model 2048, 16 heads; MLA with kv_lora_rank 512, q/k 128 + 64 RoPE
dims per head, v 128, no q LoRA; the first layer dense (d_ff 11264), then
26 MoE layers of 64 routed experts (width 1408, 6 per token, sigmoid
router with a selection bias, top-6 weights normalised and scaled by
2.446) and 2 shared experts; vocab 163840, untied; RoPE theta 50000.

``CONFIG`` is one chip's share of an 8-chip expert-parallel pod: it holds
routed experts 0-7 of each MoE layer (``experts_held``) and routes over all
64.
"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, d_ff=11264,
    vocab_size=163840, attn_kind="mla", kv_lora_rank=512, qk_nope_dim=128,
    qk_rope_dim=64, v_head_dim=128, rope_theta=50000.0, norm_eps=1e-5,
    n_experts=64, top_k=6, moe_d_ff=1408, n_shared_experts=2,
    first_dense_layers=1, router="sigmoid_bias", routed_scale=2.446,
    experts_held=(0, 8),
)

SMOKE = ModelConfig(
    name="moonlight-smoke", family="moe",
    n_layers=5, d_model=64, n_heads=4, n_kv_heads=4, d_ff=160,
    vocab_size=512, attn_kind="mla", kv_lora_rank=32, qk_nope_dim=16,
    qk_rope_dim=8, v_head_dim=16, rope_theta=50000.0,
    n_experts=8, top_k=3, moe_d_ff=32, n_shared_experts=2,
    first_dense_layers=1, router="sigmoid_bias", routed_scale=2.446,
    experts_held=(0, 4), exit_layers=(2, 4, 5), dtype="float32",
    param_dtype="float32", remat=False, vocab_pad_multiple=16,
)
