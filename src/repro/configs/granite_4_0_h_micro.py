"""Granite-4.0-H-Micro — hybrid decoder: Mamba-2 layers with a few GQA
attention layers (no positional embedding), every layer followed by a
dense SwiGLU MLP, Granite's embedding / residual / attention / logits
multipliers [hf:ibm-granite/granite-4.0-h-micro config.json]."""
from repro.configs.base import ArchConfig, register

# [m m m m m a m m m m] x 4: 36 Mamba-2 layers, 4 attention layers
LAYER_TYPES = (("mamba",) * 5 + ("attention",) + ("mamba",) * 4) * 4

CONFIG = register(ArchConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab_size=100352,
    act="swiglu",
    rope="none",
    rope_theta=10_000.0,
    layer_types=LAYER_TYPES,
    mamba_heads=64,
    mamba_head_dim=64,
    mamba_state=128,
    mamba_groups=1,
    mamba_conv=4,
    mamba_chunk=256,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.015625,
    logits_scaling=8.0,
    norm_eps=1e-5,
    tie_embeddings=True,
    remat=False,
    source="hf:ibm-granite/granite-4.0-h-micro",
))
