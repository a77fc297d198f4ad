"""phi3-mini-3.8b [dense] — RoPE SwiGLU MHA, sliding window [arXiv:2404.14219].

Phi-3-mini-4k (microsoft/Phi-3-mini-4k-instruct, config.json): 32L
d_model=3072, 32 heads over 32 KV heads (MHA, head 96), d_ff=8192,
vocab=32064, 4096-token context, RoPE theta 1e4, RMSNorm eps 1e-5, no q/k/v
bias, untied LM head, and a 2047-token sliding window (a key is visible to
query q iff q - 2047 < k <= q).
"""
from __future__ import annotations

from dataclasses import replace

from ..models.config import ModelConfig

CONFIG = ModelConfig(
    name="phi3-mini-3.8b",
    arch_type="dense",
    n_layers=32,
    d_model=3072,
    n_heads=32,
    n_kv_heads=32,
    d_ff=8192,
    vocab_size=32064,
    rope_theta=10_000.0,
    norm_eps=1e-5,
    qkv_bias=False,
    tie_embeddings=False,
    sliding_window=2047,
    source="arXiv:2404.14219; huggingface.co/microsoft/Phi-3-mini-4k-instruct",
)


def smoke_config() -> ModelConfig:
    return replace(
        CONFIG, n_layers=2, d_model=128, n_heads=4, n_kv_heads=4, d_ff=320,
        vocab_size=512, stages=(),
    )
