"""Shared layers: norms, RoPE, MLP, initializers.

Weight layout convention (matches ``core.tp.PartitionPlan``): every
projection is stored ``(d_in, d_out)`` and applied as ``x @ w``.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp


Params = Dict[str, Any]


# ----------------------------------------------------------------------
# initializers
# ----------------------------------------------------------------------

def dense_init(key: jax.Array, d_in: int, d_out: int,
               dtype: Any) -> jax.Array:
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32)
            * scale).astype(dtype)


def embed_init(key: jax.Array, vocab: int, d: int, dtype: Any) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# ----------------------------------------------------------------------
# norms
# ----------------------------------------------------------------------

def rms_norm(x: jax.Array, gain: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps)).astype(dt)
            * (1.0 + gain.astype(dt)))


def layer_norm(x: jax.Array, gain: jax.Array, bias: jax.Array,
               eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(dt) * gain.astype(dt) + bias.astype(dt)


# ----------------------------------------------------------------------
# RoPE
# ----------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (...,S,D/2)
    sin = jnp.sin(angles)[..., None, :]                # (...,S,1,D/2)
    cos = jnp.cos(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------
# MLP
# ----------------------------------------------------------------------

def init_mlp(key: jax.Array, d: int, f: int, act: str, dtype: Any) -> Params:
    ks = jax.random.split(key, 3)
    if act == "silu":
        return {"w_gate": dense_init(ks[0], d, f, dtype),
                "w_up": dense_init(ks[1], d, f, dtype),
                "w_down": dense_init(ks[2], f, d, dtype)}
    return {"w_up": dense_init(ks[0], d, f, dtype),
            "w_down": dense_init(ks[1], f, d, dtype)}


def proj(x: jax.Array, w: Any, qmm=None) -> jax.Array:
    """``x @ w`` with an optional quantized-matmul hook: the serving
    runner's Q4_0 mode passes ``qmm`` (``repro.quant.policy.make_qmm``)
    so projection leaves may be packed-code subtrees instead of dense
    arrays; every other path leaves ``qmm=None`` and pays nothing."""
    return x @ w if qmm is None else qmm(x, w)


def mlp(params: Params, x: jax.Array, act: str, qmm=None) -> jax.Array:
    if act == "silu":
        h = jax.nn.silu(proj(x, params["w_gate"], qmm)) \
            * proj(x, params["w_up"], qmm)
    else:
        h = jax.nn.gelu(proj(x, params["w_up"], qmm))
    return proj(h, params["w_down"], qmm)


# ----------------------------------------------------------------------
# logits
# ----------------------------------------------------------------------

def unembed(embed_or_head: jax.Array, x: jax.Array, *,
            tied: bool) -> jax.Array:
    """Logits in float32 whatever the model dtype: rounded to bf16, the
    top two of a 150k-entry vocab tie exactly at a few percent of
    positions, and greedy argmax then follows rounding noise."""
    head = embed_or_head.T if tied else embed_or_head
    return jnp.matmul(x, head, preferred_element_type=jnp.float32)


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  ignore_id: int = -100) -> jax.Array:
    """Mean token-level cross entropy, fp32, with label masking."""
    lf = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(
        lf, jnp.clip(labels, 0)[..., None], axis=-1)[..., 0]
    nll = logz - gold
    mask = (labels != ignore_id).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
