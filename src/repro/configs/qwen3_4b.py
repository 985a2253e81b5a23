"""qwen3-4b [dense] — qk_norm, GQA [hf:Qwen/Qwen3-4B; hf]."""
from .base import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="qwen3-4b", family="dense",
        layers=36, d_model=2560, heads=32, kv_heads=8, head_dim=128,
        d_ff=9728, vocab=151936,
        norm="rms", act="silu", glu=True, qk_norm=True,
        rope_theta=1_000_000.0, tie_embeddings=True,
    )


def smoke() -> ModelConfig:
    return ModelConfig(
        name="qwen3-smoke", family="dense",
        layers=2, d_model=64, heads=8, kv_heads=2, head_dim=16,
        d_ff=128, vocab=512,
        norm="rms", act="silu", glu=True, qk_norm=True, tie_embeddings=True,
    )
