"""jit'd dispatch wrappers for the Pallas kernels.

On TPU the wrappers call the compiled kernels; on CPU (this container)
they run the kernels in interpret mode for correctness work, or fall
back to the jnp oracle for speed (``impl="ref"``).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional

import jax

from . import ref as _ref
from .decode_attention import decode_attention as _decode_attention_kernel
from .decode_attention import \
    paged_decode_attention as _paged_decode_attention_kernel
from .q4_gemm import q4_gemm as _q4_gemm_kernel
from .rglru_scan import rglru_scan_kernel as _rglru_scan_kernel


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("impl", "block_n", "block_k"))
def q4_matmul(x: jax.Array, packed: jax.Array, scales: jax.Array, *,
              impl: str = "auto", block_n: int = 256,
              block_k: int = 256) -> jax.Array:
    """Quantized matmul: x (M,K) @ W_q4 (K,N) -> (M,N) f32."""
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return _ref.q4_gemm_ref(x, packed, scales)
    return _q4_gemm_kernel(x, packed, scales, block_n=block_n,
                           block_k=block_k, interpret=not _on_tpu())


def _score_scale(scale: Optional[float], D: int) -> float:
    """The score scale a caller gave, or 1/sqrt(D) when it gave none."""
    return 1.0 / math.sqrt(D) if scale is None else float(scale)


@functools.partial(jax.jit, static_argnames=("impl", "block_s", "scale"))
def gqa_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         kv_len: Any, *, impl: str = "auto",
                         block_s: int = 512,
                         scale: Optional[float] = None) -> jax.Array:
    """Flash-decoding for one token with GQA.

    q (B,1,Hq,D); k,v (B,S,Hkv,D) -> out (B,1,Hq,D), matching the
    model-zoo attention contract.  ``scale`` multiplies the scores
    (None: 1/sqrt(D))."""
    B, one, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qk = q.reshape(B, Hkv, G, D)
    scale = _score_scale(scale, D)
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        out = _ref.decode_attention_ref(qk, k, v, kv_len, scale=scale)
    else:
        out = _decode_attention_kernel(qk, k, v, kv_len, scale=scale,
                                       block_s=block_s,
                                       interpret=not _on_tpu())
    return out.reshape(B, 1, Hq, D)


@functools.partial(jax.jit, static_argnames=("page_size", "impl",
                                             "softcap", "scale"))
def paged_gqa_decode_attention(q: jax.Array, k_pool: jax.Array,
                               v_pool: jax.Array, block_tables: jax.Array,
                               kv_lens: jax.Array, window=0, *,
                               page_size: int, softcap: float = 0.0,
                               scale: Optional[float] = None,
                               impl: str = "auto",
                               k_scale=None, v_scale=None) -> jax.Array:
    """Paged flash-decoding for one token per sequence with GQA.

    q (B,1,Hq,D); k_pool,v_pool (n_pages*page_size,Hkv*D) — ONE layer's
    flat page-pool buffer, exactly as the per-layer paged cache holds it
    (``Model.init_cache(page_size=...)``); the paged view
    (n_pages, page_size, Hkv*D) is a free reshape here.  block_tables
    (B,max_pages); kv_lens (B,) -> out (B,1,Hq,D).  The device-side
    read path of the serving KV pool (``repro.serving.kv_pool``): K/V
    are addressed *through* the block table, so batch membership and
    sequence length change without recompilation or cache copies.
    The kernel reads and scores only the pages that hold a lane's
    visible slots (``kv_len`` and the window), so its work follows the
    live context, not ``max_pages``; a lane with ``kv_len`` 0 scores
    nothing and returns 0.  ``scale`` multiplies the scores (None:
    1/sqrt(D); Granite's ``attention_multiplier`` otherwise).

    ``k_scale``/``v_scale`` ((n_pages*page_size, Hkv) f32) select the
    **int8 page** format (``--kv-dtype int8``): the pools hold int8
    codes with per-(row, head) scales, dequantized after the block-table
    gather (O(touched bytes)).  The quantized read currently routes
    through the jnp reference path on every backend — teaching the
    Pallas paged kernel to dequantize in-tile is listed future work
    (``docs/quantization.md``).
    """
    B, one, Hq, D = q.shape
    Hkv = k_pool.shape[1] // D
    G = Hq // Hkv
    n_pages = k_pool.shape[0] // page_size
    # all shapes here may be the TP-local slice: under the head-sharded
    # serving mesh (serving.runner mesh mode) this runs inside
    # shard_map with Hq/Hkv divided by the shard count and the pool
    # buffer holding only the local kv heads — the block-table gather
    # is identical, the GQA group size G is shard-invariant, and no
    # collective appears at this level (the head merge happens in the
    # transformer, once per layer)
    k_pages = k_pool.reshape(n_pages, page_size, Hkv * D)
    v_pages = v_pool.reshape(n_pages, page_size, Hkv * D)
    qk = q.reshape(B, Hkv, G, D)
    scale = _score_scale(scale, D)
    if k_scale is not None or v_scale is not None:
        ks = k_scale.reshape(n_pages, page_size, Hkv)
        vs = v_scale.reshape(n_pages, page_size, Hkv)
        out = _ref.paged_decode_attention_ref(qk, k_pages, v_pages,
                                              block_tables, kv_lens, window,
                                              softcap=softcap, scale=scale,
                                              k_scales=ks, v_scales=vs)
    elif impl == "ref" or (impl == "auto" and not _on_tpu()):
        out = _ref.paged_decode_attention_ref(qk, k_pages, v_pages,
                                              block_tables, kv_lens, window,
                                              softcap=softcap, scale=scale)
    else:
        out = _paged_decode_attention_kernel(qk, k_pages, v_pages,
                                             block_tables, kv_lens, window,
                                             scale=scale, softcap=softcap,
                                             interpret=not _on_tpu())
    return out.reshape(B, 1, Hq, D).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("impl", "block_t"))
def rglru_linear_scan(a: jax.Array, u: jax.Array, h0=None, *,
                      impl: str = "auto", block_t: int = 128) -> jax.Array:
    """RG-LRU recurrence h[t] = a[t]*h[t-1] + u[t] over (B, T, W)."""
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return _ref.rglru_scan_ref(a, u, h0)
    return _rglru_scan_kernel(a, u, h0=h0, block_t=block_t,
                              interpret=not _on_tpu())
