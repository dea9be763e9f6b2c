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


def _online_softmax_update(h, q, k, v, acc_ref, m_ref, l_ref, mask, *,
                           scale: float, softcap: float = 0.0):
    """One KV block's online-softmax update for kv head ``h``.

    ``q`` (G, D), ``k``/``v`` (BS, D), scratch ``acc`` (H, G, D) and
    ``m``/``l`` (H, G, 1); ``mask`` (1, BS) marks the block's visible
    slots.  The MXU takes q, k, v in their own dtype and the
    probabilities in V's, accumulating in f32 — the operands
    ``models.attention.flash_attention`` uses."""
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(mask, s, NEG_INF)                    # (G, BS)

    m_prev = m_ref[h]                                  # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[h] = m_new


def _online_softmax_heads(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                          mask, *, scale: float, softcap: float = 0.0):
    """``_online_softmax_update`` for every kv head of a ``(1, BS, H,
    D)`` block, with ``q_ref`` (1, H, G, D)."""
    for h in range(q_ref.shape[1]):
        _online_softmax_update(h, q_ref[0, h], k_ref[0, :, h, :],
                               v_ref[0, :, h, :], acc_ref, m_ref, l_ref,
                               mask, scale=scale, softcap=softcap)


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

#: tokens of context one block of the paged walk holds; the pages a
#: block fetches follow from the page size (``pages_per_block``).  On a
#: v5e 128 beat 64 and 256 at both benchmark cells' decode shapes
#: (``tools/paged_kernel_steps.py``)
BLOCK_TOKENS = 128

# rows of the paged walk's per-lane plan (``_walk_plan``)
_FIRST, _LAST, _N_BLOCKS, _START, _NEXT = range(5)


def pages_per_block(page_size: int, max_pages: int) -> int:
    """Pages the paged kernel fetches per block: ``BLOCK_TOKENS`` worth,
    at least one, at most a lane's whole block table."""
    return min(max_pages, max(1, BLOCK_TOKENS // page_size))


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


def _walk_plan(kv_lens, window, page_size: int, n_pages: int, ppb: int):
    """The walk's plan, (5, B + 1) int32, a column per lane: its first
    and last visible page, the blocks it fetches
    (``cdiv(last - first + 1, ppb)``, 0 for an idle lane), the walk's
    count of blocks before its first (whose parity names the buffer slot
    that block lands in), and in the next row the first lane at or after
    column ``i`` with a block to fetch (B if none): column 0 is the lane
    the walk starts with, column ``b + 1`` the lane whose first block
    lane ``b``'s last block prefetches."""
    B = kv_lens.shape[0]
    first, last = _visible_pages(kv_lens, window, page_size, n_pages)
    n_blocks = jnp.where(kv_lens > 0, (last - first + ppb) // ppb, 0)
    start = jnp.cumsum(n_blocks) - n_blocks
    lanes = jnp.arange(B, dtype=jnp.int32)
    nxt = jax.lax.cummin(jnp.where(n_blocks > 0, lanes, B), reverse=True)
    pad = jnp.zeros((1,), jnp.int32)
    return jnp.stack([
        jnp.concatenate([first, pad]), jnp.concatenate([last, pad]),
        jnp.concatenate([n_blocks, pad]), jnp.concatenate([start, pad]),
        jnp.concatenate([nxt, jnp.full((1,), B, jnp.int32)]),
    ]).astype(jnp.int32)


def _paged_decode_attn_kernel(bt_ref, len_ref, win_ref, plan_ref,
                              q_ref, k_hbm, v_hbm, o_ref,
                              k_buf, v_buf, sems, acc_ref, m_ref, l_ref, *,
                              page_size: int, ppb: int, scale: float,
                              softcap: float):
    b = pl.program_id(0)
    n_lanes = pl.num_programs(0)
    H, D = q_ref.shape[1], q_ref.shape[3]
    rows = ppb * page_size

    def fetch(lane, j, slot, *, wait: bool):
        """Start (or wait for) the copies of ``lane``'s block ``j`` into
        buffer ``slot``: one K and one V copy per page up to the lane's
        last visible page; the rows of pages past it are not written."""
        base = plan_ref[_FIRST, lane] + j * ppb
        last = plan_ref[_LAST, lane]
        for i in range(ppb):
            @pl.when(base + i <= last)
            def _page():
                page = bt_ref[lane, base + i]
                for pool, buf, s in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)):
                    cp = pltpu.make_async_copy(
                        pool.at[page],
                        buf.at[slot, pl.ds(i * page_size, page_size)],
                        sems.at[s, slot])
                    if wait:
                        cp.wait()
                    else:
                        cp.start()

    @pl.when(b == 0)
    def _start_walk():
        head = plan_ref[_NEXT, 0]

        @pl.when(head < n_lanes)
        def _():
            fetch(head, 0, 0, wait=False)

    _init_scratch(acc_ref, m_ref, l_ref)
    kv_len = len_ref[b]
    window = win_ref[0]
    n_blocks = plan_ref[_N_BLOCKS, b]
    nxt = plan_ref[_NEXT, b + 1]
    # logical position of the lane's first fetched slot, and the end of
    # what it may score: past the last visible page nothing was fetched
    pos0 = plan_ref[_FIRST, b] * page_size
    end = jnp.minimum(kv_len, (plan_ref[_LAST, b] + 1) * page_size)

    def block(j, carry):
        slot = (plan_ref[_START, b] + j) % 2

        # the next block's copies, the next live lane's first at this
        # lane's last, run while this block is scored
        @pl.when(j + 1 < n_blocks)
        def _():
            fetch(b, j + 1, 1 - slot, wait=False)

        @pl.when((j + 1 == n_blocks) & (nxt < n_lanes))
        def _():
            fetch(nxt, 0, 1 - slot, wait=False)

        fetch(b, j, slot, wait=True)
        row0 = pos0 + j * rows
        kpos = row0 + jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        mask = (kpos < end) & ((window <= 0) | (kpos > kv_len - 1 - window))
        # rows not scored may hold anything, NaN included, and a zero
        # probability times NaN is NaN: select them out of V
        vpos = row0 + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        v_mask = (vpos < end) & ((window <= 0) | (vpos > kv_len - 1 - window))
        for h in range(H):
            cols = pl.ds(h * D, D)
            v = v_buf[slot, :, cols]
            _online_softmax_update(
                h, q_ref[0, h], k_buf[slot, :, cols],
                jnp.where(v_mask, v, jnp.zeros_like(v)),
                acc_ref, m_ref, l_ref, mask, scale=scale, softcap=softcap)
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    _write_out(o_ref, acc_ref, l_ref)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_tables: jax.Array,
                           kv_lens: jax.Array, window=0, *,
                           scale: float, softcap: float = 0.0,
                           interpret: bool = True) -> jax.Array:
    """q (B,H,G,D) × page pool k,v (P,ps,H·D) -> out (B,H,G,D) f32;
    scores are ``q·k * scale`` (before ``softcap``).

    ``k_pages``/``v_pages`` are the paged view of ONE layer's flat pool
    buffer — the ops wrapper (``repro.kernels.ops``) reshapes the
    per-layer (P*ps, H·D) cache buffer before dispatching here, which is
    free: the view keeps the buffer's last dim, over which a TPU tiles
    it.  Each kv head's keys are the lane-aligned columns
    ``[h·D, (h+1)·D)`` of a page.  H is whatever the pool holds: all kv
    heads, or one shard's slice of them under the head-sharded serving
    mesh.  The pools stay in HBM.

    The walk: the grid is one step per lane, and the kernel copies the
    lane's pages itself.  ``block_tables`` (B, max_pages) int32,
    ``kv_lens`` (B,) int32, the window and the walk's plan
    (``_walk_plan``: each lane's first and last visible page, from
    ``kv_len`` and the window) are scalar-prefetched.  A lane's visible
    pages go in blocks of ``pages_per_block`` pages (``BLOCK_TOKENS``
    tokens), one DMA per physical page into a double-buffered VMEM
    block; block j + 1's copies, or at a lane's last block the next live
    lane's first, run while block j is scored.  Each kv head scores a
    block in one (G, block) matmul.  No page outside a lane's visible
    range is fetched; the rows of a last block past the lane's last page
    are never written and are masked out of both the scores and V.  A
    lane with ``kv_len`` 0 (an idle batch slot) fetches nothing and
    returns 0.
    """
    B, H, G, D = q.shape
    _, page_size, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    ppb = pages_per_block(page_size, max_pages)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    kv_lens = jnp.asarray(kv_lens, jnp.int32).reshape(B)
    window = jnp.asarray(window, jnp.int32).reshape(1)
    # once per call: integer divisions in the kernel's scalar code are
    # slow on a v5e
    plan = _walk_plan(kv_lens, window[0], page_size, max_pages, ppb)

    kernel = functools.partial(_paged_decode_attn_kernel,
                               page_size=page_size, ppb=ppb,
                               scale=scale, softcap=softcap)
    lane = pl.BlockSpec((1, H, G, D), lambda b, *_: (b, 0, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # block tables, kv lens, window, the walk's plan
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[lane, pool, pool],
        out_specs=lane,
        scratch_shapes=[
            pltpu.VMEM((2, ppb * page_size, H * D), k_pages.dtype),
            pltpu.VMEM((2, ppb * page_size, H * D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ] + _scratch(H, G, D),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, G, D), jnp.float32),
        # copies started in one grid step are waited on in the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # the kernel's instruction name in the compiled program and the
        # device trace (``%paged_gqa_decode_attention.N``), whatever
        # wrappers it is called through
        name="paged_gqa_decode_attention",
    )(block_tables, kv_lens, window, plan, q, k_pages, v_pages)
