"""ChatGLM3-6B [arXiv:2406.12793] as published (THUDM/chatglm3-6b
config.json): GQA(kv=2), bias on the fused q/k/v projection, RMSNorm at
eps 1e-5, GLM-style partial ("2d") rotary on half the head dim, swiGLU
FFN, untied output layer.

The published code pairs adjacent rotary dims (2i, 2i+1); ``rope2d``
pairs (i, i + 32). A checkpoint maps onto this layout by permuting the
rotary columns of each head of the q and k projections (and their
biases) once, at load time."""
from repro.config.base import ModelConfig
from repro.config.registry import register

CONFIG = register(ModelConfig(
    name="chatglm3-6b",
    family="dense",
    source="arXiv:2406.12793",
    n_layers=28,
    d_model=4096,
    n_heads=32,
    n_kv_heads=2,
    head_dim=128,
    d_ff=13_696,
    vocab_size=65_024,
    rope="rope2d",
    qkv_bias=True,
    activation="silu",
    norm="rmsnorm",
    norm_eps=1e-5,
))
