"""granite-3-8b [dense] — GQA with Granite's four muP scalars.
[hf:ibm-granite/granite-3.0-8b-base]

A Llama block at 40 layers, d 4096, 32/8 heads x 128, d_ff 12,800,
vocab 49,155, tied embeddings, plus the scalars of
``GraniteForCausalLM``: the embedding times 12, scores times 1/128 in
place of 1/sqrt(128), each residual branch times 0.22, and the logits
divided by 16.
"""

import jax.numpy as jnp

from ..models.config import ModelConfig

CONFIG = ModelConfig(
    name="granite-3-8b",
    arch_type="dense",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=12_800,
    vocab_size=49_155,
    rope_theta=10_000.0,
    norm_eps=1e-5,
    tie_embeddings=True,
    attention_multiplier=0.0078125,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    long_context="sliding_window",     # full-attention arch: long_500k
    long_context_window=16_384,        # runs only under this window (SW)
    remat=True,
    dtype=jnp.bfloat16,
    source="hf:ibm-granite/granite-3.0-8b-base",
)
