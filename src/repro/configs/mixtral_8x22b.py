"""mixtral-8x22b — MoE 8 experts top-2, sliding-window attention
[arXiv:2401.04088].

56L, d_model 6144, 48 heads (GQA kv=8, head_dim 128), expert d_ff 16384,
vocab 32768.
"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    train_microbatches=8,
    name="mixtral-8x22b", family="moe",
    n_layers=56, d_model=6144, n_heads=48, n_kv_heads=8, d_ff=16384,
    vocab_size=32768, head_dim=128, sliding_window=4096,
    n_experts=8, top_k=2,
)

SMOKE = ModelConfig(
    name="mixtral22-smoke", family="moe",
    n_layers=4, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
    vocab_size=512, head_dim=16, sliding_window=16,
    n_experts=4, top_k=2,
    exit_layers=(2, 3, 4), dtype="float32", param_dtype="float32", remat=False,
    vocab_pad_multiple=16,
)
