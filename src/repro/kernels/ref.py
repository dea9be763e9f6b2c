"""Pure-jnp oracles for every Pallas kernel (the ground truth in tests)."""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..quant.q4_0 import dequantize


def q4_gemm_ref(x: jax.Array, packed: jax.Array,
                scales: jax.Array) -> jax.Array:
    """x (M,K) @ dequant(packed, scales) (K,N) -> (M,N) f32."""
    w = dequantize(packed, scales, dtype=jnp.float32)
    return x.astype(jnp.float32) @ w


def decode_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                         kv_len, *, scale: Optional[float] = None
                         ) -> jax.Array:
    """q (B,H,G,D) × cache k,v (B,S,H,D) -> (B,H,G,D) f32; scores
    ``q·k * scale`` (None: 1/sqrt(D))."""
    B, H, G, D = q.shape
    S = k.shape[1]
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    s = jnp.einsum("bhgd,bshd->bhgs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = jnp.arange(S) < jnp.asarray(kv_len)
    s = jnp.where(mask[None, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgs,bshd->bhgd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def paged_decode_attention_ref(q: jax.Array, k_pages: jax.Array,
                               v_pages: jax.Array, block_tables: jax.Array,
                               kv_lens: jax.Array,
                               window=0, softcap: float = 0.0,
                               k_scales=None, v_scales=None, *,
                               scale: Optional[float] = None) -> jax.Array:
    """Gather-based paged flash-decoding oracle.

    q (B,H,G,D) one token per sequence; k_pages/v_pages (P,ps,H,D) or
    (P,ps,H*D), the shared physical page pool; block_tables
    (B,max_pages) maps each sequence's logical page j to a physical
    page id; kv_lens (B,) is the per-sequence token count (logical
    positions are contiguous 0..len-1, unlike the ring cache).  A row
    with no visible slot (kv_len == 0, an idle batch slot) returns 0,
    as the Pallas kernel does.  Scores are ``q·k * scale`` (None:
    1/sqrt(D)).

    ``k_scales``/``v_scales`` (P, ps, H) switch on the **int8 page**
    format (``repro.quant.kv_int8``): pages hold int8 codes and the
    per-(token, head) scales are gathered through the same block table,
    so dequantization costs O(gathered bytes), never O(pool bytes).
    """
    B, H, G, D = q.shape
    ps = k_pages.shape[1]
    max_pages = block_tables.shape[1]
    L = max_pages * ps
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    # gather each sequence's pages, flatten to its logical KV view
    k = k_pages[block_tables].reshape(B, L, H, D)
    v = v_pages[block_tables].reshape(B, L, H, D)
    if k_scales is not None:
        k = k.astype(jnp.float32) \
            * k_scales[block_tables].reshape(B, L, H)[..., None]
    if v_scales is not None:
        v = v.astype(jnp.float32) \
            * v_scales[block_tables].reshape(B, L, H)[..., None]
    s = jnp.einsum("bhgd,bshd->bhgs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    j = jnp.arange(L)
    valid = j[None, :] < kv_lens[:, None]
    w = jnp.asarray(window, jnp.int32)
    qpos = kv_lens[:, None] - 1
    valid &= (w <= 0) | (j[None, :] > qpos - w)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid.any(axis=1)[:, None, None, None], p, 0.0)
    return jnp.einsum("bhgs,bshd->bhgd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def rglru_scan_ref(a: jax.Array, u: jax.Array, h0=None) -> jax.Array:
    """Associative-scan oracle for the RG-LRU recurrence kernel."""
    if h0 is not None:
        u = u.at[:, 0].add(a[:, 0] * h0)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, b1 * a2 + b2

    _, h = jax.lax.associative_scan(combine, (a, u), axis=1)
    return h
