"""int8 KV-page quantization for the paged serving cache.

The paged pool stores each layer's K/V as a flat row buffer
((n_pages * page_size, Hkv * D) — see ``Model.init_cache``).  Under
``kv_dtype="int8"`` the same rows hold int8 codes plus one f32 scale
per **(row, kv head)**:

    k / v           (rows, Hkv * D) int8  code = round(x / scale)
    k_scale/v_scale (rows, Hkv)    f32    scale = max|x| / 127

Per-(token, head) scales — not per-page — because pages fill one token
row at a time (prefill scatters a chunk, decode scatters a single row
per sequence): a page-granular scale would have to be rewritten, and
every code in the page requantized, on each append.  Row scales make
the write path a pure scatter, identical in shape to the fp32 path,
and cost 4 bytes per head per token next to D bytes of codes:

    bytes/token/head:  fp32  4·D        int8  D + 4

so a page shrinks by 4D/(D+4) ≈ 3.8x at D = 64 (the capacity lever —
``KVPoolConfig.page_bytes`` does this arithmetic for the planner).

Dequantization happens only on the **read** side, after the block-table
gather, so per-step cost stays O(touched bytes) — the pool is never
dequantized wholesale (``repro.kernels.ops.paged_gqa_decode_attention``
and the resumed-prefill gather in ``models.transformer._paged_attn``).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def quantize_rows(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(..., H, D) float -> ((..., H, D) int8 codes, (..., H) f32 scales).

    Symmetric absmax scaling per (row, head); all-zero rows (idle batch
    lanes writing the scratch page) get scale 0 and dequantize to 0.
    """
    xf = jnp.asarray(x, jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)                    # (..., H)
    scale = absmax / 127.0
    inv = jnp.where(scale > 0.0, 1.0 / jnp.where(scale > 0.0, scale, 1.0),
                    0.0)
    q = jnp.clip(jnp.round(xf * inv[..., None]), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_rows(q: jax.Array, scale: jax.Array,
                    dtype=jnp.float32) -> jax.Array:
    """((..., H, D) int8, (..., H) f32) -> (..., H, D) ``dtype``."""
    return (q.astype(jnp.float32)
            * scale.astype(jnp.float32)[..., None]).astype(dtype)


def kv_bytes_per_row_head(head_dim: int) -> int:
    """Pool bytes one (token, kv head) costs: D code bytes + 4 scale."""
    return head_dim + 4
