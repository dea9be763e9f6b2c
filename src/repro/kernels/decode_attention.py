"""Pallas TPU kernel: flash-decoding attention (one query token).

The decode hot-spot: a single query attends over a long KV cache —
pure HBM bandwidth (read every cache byte once), exactly the workload
ArcLight's NUMA placement targets.  TPU adaptation: the cache streams
HBM→VMEM in (BS, H, D) chunks along the sequence grid axis; online
softmax state (m, l, acc) per kv head lives in VMEM scratch across grid
steps; the final grid step normalises and writes out.

Shapes (GQA folded outside the kernel by the ops wrapper):
    q   (B, H, G, D)   one token's queries, G = Hq // Hkv
    k,v (B, S, H, D)   cache (H = kv heads)
    kv_len scalar      number of valid cache slots (rest masked)

Grid: (B, S/BS) — the sequence axis is innermost so scratch
accumulates per batch row.  Every block spans the whole kv-head axis:
Mosaic tiles the last two dims of a block, and a block of one head out
of H (``(BS, 1, D)``) is neither a multiple of the sublane tile nor the
array's full extent, so the chip's compiler refuses it.  The kernel
walks the H heads of a block with a static loop instead.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _online_softmax_heads(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                          mask, *, scale: float, softcap: float = 0.0):
    """One KV block's online-softmax update for every kv head.

    ``q_ref`` (1, H, G, D), ``k_ref``/``v_ref`` (1, BS, H, D), scratch
    ``acc`` (H, G, D) and ``m``/``l`` (H, G, 1); ``mask`` (1, BS) marks
    the block's visible slots.  The MXU takes q, k, v in their own
    dtype and the probabilities in V's, accumulating in f32 — the
    operands ``models.attention.flash_attention`` uses."""
    for h in range(q_ref.shape[1]):
        q = q_ref[0, h]                                # (G, D)
        k = k_ref[0, :, h, :]                          # (BS, D)
        v = v_ref[0, :, h, :]                          # (BS, D)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(mask, s, NEG_INF)                # (G, BS)

        m_prev = m_ref[h]                              # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[h] = m_new


def _init_scratch(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _write_out(o_ref, acc_ref, l_ref):
    denom = l_ref[...]
    denom = jnp.where(denom > 0, denom, 1.0)
    o_ref[0] = acc_ref[...] / denom


def _scratch(H: int, G: int, D: int):
    return [pltpu.VMEM((H, G, D), jnp.float32),
            pltpu.VMEM((H, G, 1), jnp.float32),
            pltpu.VMEM((H, G, 1), jnp.float32)]


def _decode_attn_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                        acc_ref, m_ref, l_ref, *,
                        block_s: int, n_s: int, scale: float):
    s_idx = pl.program_id(1)

    @pl.when(s_idx == 0)
    def _init():
        _init_scratch(acc_ref, m_ref, l_ref)

    kpos = s_idx * block_s + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_s), 1)
    _online_softmax_heads(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                          kpos < len_ref[0], scale=scale)

    @pl.when(s_idx == n_s - 1)
    def _finalize():
        _write_out(o_ref, acc_ref, l_ref)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     kv_len, *, scale: float, block_s: int = 512,
                     interpret: bool = True) -> jax.Array:
    """q (B,H,G,D) × cache k,v (B,S,H,D) -> out (B,H,G,D) f32; scores
    are ``q·k * scale``."""
    B, H, G, D = q.shape
    _, S, _, _ = k.shape
    block_s = min(block_s, S)
    if S % block_s:
        raise ValueError(f"S={S} not divisible by block_s={block_s}")
    n_s = S // block_s
    kv_len = jnp.asarray(kv_len, jnp.int32).reshape(1)

    kernel = functools.partial(_decode_attn_kernel, block_s=block_s,
                               n_s=n_s, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                     # kv_len in SMEM
        grid=(B, n_s),
        in_specs=[
            pl.BlockSpec((1, H, G, D), lambda b, s, _: (b, 0, 0, 0)),
            pl.BlockSpec((1, block_s, H, D), lambda b, s, _: (b, s, 0, 0)),
            pl.BlockSpec((1, block_s, H, D), lambda b, s, _: (b, s, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, G, D), lambda b, s, _: (b, 0, 0, 0)),
        scratch_shapes=_scratch(H, G, D),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, G, D), jnp.float32),
        interpret=interpret,
    )(kv_len, q, k, v)


# ----------------------------------------------------------------------
# paged variant: K/V live in a shared page pool, read through per-
# sequence block tables (the serving KV pool's device layout)
# ----------------------------------------------------------------------

def _visible_pages(kv_lens, window, page_size: int, n_pages: int):
    """Each lane's first and last logical page holding a slot visible to
    its query at ``kv_len - 1``: slots ``[kv_len - window, kv_len)``
    under a sliding window, ``[0, kv_len)`` without one.  A lane with no
    visible slot (``kv_len`` 0) gets ``first == last == 0``."""
    last = jnp.clip((kv_lens + page_size - 1) // page_size - 1, 0,
                    n_pages - 1)
    first = jnp.where(window > 0,
                      jnp.maximum(kv_lens - window, 0) // page_size, 0)
    return jnp.minimum(first, last), last


def _paged_decode_attn_kernel(bt_ref, len_ref, win_ref, first_ref, last_ref,
                              q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                              l_ref, *,
                              page_size: int, n_pages: int, scale: float,
                              softcap: float):
    b = pl.program_id(0)
    p_idx = pl.program_id(1)

    @pl.when(p_idx == 0)
    def _init():
        _init_scratch(acc_ref, m_ref, l_ref)

    kv_len = len_ref[b]
    window = win_ref[0]

    # pages outside [first, last] hold no visible slot: the index maps
    # name the nearest visible page there, so no DMA is issued, and the
    # scores are skipped; a lane with no visible slot skips every page
    @pl.when((kv_len > 0) & (p_idx >= first_ref[b])
             & (p_idx <= last_ref[b]))
    def _attend():
        # logical (not physical) positions of this page's slots
        kpos = p_idx * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        mask = kpos < kv_len
        mask &= (window <= 0) | (kpos > kv_len - 1 - window)
        _online_softmax_heads(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                              mask, scale=scale, softcap=softcap)

    @pl.when(p_idx == n_pages - 1)
    def _finalize():
        _write_out(o_ref, acc_ref, l_ref)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_tables: jax.Array,
                           kv_lens: jax.Array, window=0, *,
                           scale: float, softcap: float = 0.0,
                           interpret: bool = True) -> jax.Array:
    """q (B,H,G,D) × page pool k,v (P,ps,H,D) -> out (B,H,G,D) f32;
    scores are ``q·k * scale`` (before ``softcap``).

    ``k_pages``/``v_pages`` are the paged view of ONE layer's flat pool
    buffer — the ops wrapper (``repro.kernels.ops``) reshapes the
    per-layer (P*ps, H, D) cache buffer before dispatching here.  H is
    whatever the pool holds: all kv heads, or one shard's slice of them
    under the head-sharded serving mesh.
    ``block_tables`` (B, max_pages) int32 and ``kv_lens`` (B,) int32 are
    scalar-prefetched so each grid step's BlockSpec index_map can DMA the
    *physical* page the sequence's logical page j maps to — the gather
    never materialises a contiguous copy of the sequence's cache.
    Grid (B, max_pages): one step reads one page of every kv head.  The
    walk is bounded by each lane's visible pages, from ``kv_len`` and the
    window (scalar-prefetched too): a step outside them names the page
    its neighbour named, so the pipeline fetches nothing, and it computes
    nothing.  A lane with ``kv_len`` 0 (an idle batch slot) scores no page
    and returns 0.
    """
    B, H, G, D = q.shape
    P, page_size, _, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    block_tables = jnp.asarray(block_tables, jnp.int32)
    kv_lens = jnp.asarray(kv_lens, jnp.int32).reshape(B)
    window = jnp.asarray(window, jnp.int32).reshape(1)
    # once per call: in every grid step's index maps and body the
    # integer divisions cost ~0.05 us a step on a v5e
    first, last = _visible_pages(kv_lens, window[0], page_size, max_pages)

    def page_map(b, p, bt, ln, w, lo, hi):
        return (bt[b, jnp.clip(p, lo[b], hi[b])], 0, 0, 0)

    kernel = functools.partial(_paged_decode_attn_kernel,
                               page_size=page_size, n_pages=max_pages,
                               scale=scale, softcap=softcap)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # block tables, kv lens, window, first and last visible pages
        num_scalar_prefetch=5,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, H, G, D), lambda b, p, *_: (b, 0, 0, 0)),
            pl.BlockSpec((1, page_size, H, D), page_map),
            pl.BlockSpec((1, page_size, H, D), page_map),
        ],
        out_specs=pl.BlockSpec((1, H, G, D), lambda b, p, *_: (b, 0, 0, 0)),
        scratch_shapes=_scratch(H, G, D),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, G, D), jnp.float32),
        interpret=interpret,
        # the kernel's instruction name in the compiled program and the
        # device trace (``%paged_gqa_decode_attention.N``), whatever
        # wrappers it is called through
        name="paged_gqa_decode_attention",
    )(block_tables, kv_lens, window, first, last, q, k_pages, v_pages)
