"""qwen3-4b [dense] — qk_norm, GQA kv=8.

[hf:Qwen/Qwen3-4B]  36L d_model=2560 32H (kv=8) d_ff=9728
vocab=151936.
"""
from repro.models import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-4b",
    arch_type="dense",
    num_layers=36,
    d_model=2560,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=9728,
    vocab_size=151936,
    activation="swiglu",
    qk_norm=True,
    rope_theta=1_000_000.0,
    block_pattern=("attn",),
    source="hf:Qwen/Qwen3-4B",
)
