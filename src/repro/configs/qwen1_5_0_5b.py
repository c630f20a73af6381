"""qwen1.5-0.5b — dense MHA with QKV bias [hf:Qwen/Qwen1.5-0.5B].

24L, d_model 1024, 16 heads (kv=16), d_ff 2816, vocab 151936, RoPE theta
1e6, RMSNorm eps 1e-6.
"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="qwen1.5-0.5b", family="dense",
    n_layers=24, d_model=1024, n_heads=16, n_kv_heads=16, d_ff=2816,
    vocab_size=151936, head_dim=64, qkv_bias=True, rope_theta=1e6,
    norm_eps=1e-6,
)

SMOKE = ModelConfig(
    name="qwen1.5-smoke", family="dense",
    n_layers=4, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
    vocab_size=512, head_dim=16, qkv_bias=True,
    exit_layers=(2, 3, 4), dtype="float32", param_dtype="float32", remat=False,
    vocab_pad_multiple=16,
)
