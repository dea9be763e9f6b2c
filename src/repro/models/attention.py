"""Attention: blockwise (flash-style) pure-JAX implementation.

One code path serves train, prefill and decode across all assigned
architectures:

* **blockwise online softmax** over KV chunks (``lax.scan``) keeps the
  activation footprint O(S·chunk) instead of O(S²) — required for the
  32k/500k shapes to fit the dry-run memory analysis;
* **GQA** by folding the query-head group into the einsum;
* **sliding window / local-global** via per-layer window metadata
  (0 = full causal);
* **decode** is the same function with Sq=1 and ``kv_len`` masking —
  flash-decoding over the cache;
* **sequence-sharded decode** (long_500k, batch=1): each shard runs
  blockwise attention over its KV slice and returns (out, m, lsum); the
  partials merge with an LSE-weighted psum (``combine_partials``) —
  ArcLight's Gather, applied to the sequence axis (beyond-paper
  optimisation, DESIGN.md §5).

The Pallas kernel in ``repro.kernels.decode_attention`` implements the
same contract for the TPU hot path; ``repro.kernels.ref`` ties the two
together in tests.
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp


NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


class AttnPartial(NamedTuple):
    """Un-normalised blockwise attention state (for cross-shard merge)."""

    out: jax.Array   # (B, Sq, Hq, D), fp32, = Σ exp(s - m) v
    m: jax.Array     # (B, Sq, Hq) running max
    lsum: jax.Array  # (B, Sq, Hq) running denominator


def _chunk_mask(qpos: jax.Array, kpos: jax.Array, *, causal: bool,
                window: jax.Array, kv_len: Optional[jax.Array],
                kpos_valid: Optional[jax.Array] = None) -> jax.Array:
    """(Sq, C) validity mask. window: scalar int32, 0 = unlimited."""
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        m &= kpos[None, :] <= qpos[:, None]
    m &= (window <= 0) | (kpos[None, :] > qpos[:, None] - window)
    if kv_len is not None:
        m &= kpos[None, :] < kv_len
    if kpos_valid is not None:
        m &= kpos_valid[None, :]
    return m


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True,
    window: Any = 0,
    q_offset: Any = 0,
    kv_offset: Any = 0,
    kv_len: Optional[Any] = None,
    kv_positions: Optional[jax.Array] = None,
    chunk: int = 512,
    softcap: float = 0.0,
    scale: Optional[float] = None,
    return_partial: bool = False,
) -> jax.Array | AttnPartial:
    """Blockwise attention.

    q (B,Sq,Hq,D); k,v (B,Skv,Hkv,D); Hq % Hkv == 0.
    ``q_offset``: absolute position of q[0] (decode: current length).
    ``kv_offset``: absolute position of k[0] (sequence-sharded caches).
    ``kv_len``: number of *globally* valid kv tokens (cache fill level).
    ``kv_positions``: explicit absolute position of every kv slot
    (ring-buffer caches); entries < 0 are masked invalid and override
    the ``kv_offset`` arithmetic.
    """
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} not divisible by Hkv={Hkv}")
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    chunk = min(chunk, Skv)
    n_chunks = -(-Skv // chunk)
    pad = n_chunks * chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if kv_len is None and kv_positions is None:
            # positions are global: this shard's valid range ends at
            # kv_offset + Skv (not Skv — kv_offset > 0 for seq shards)
            kv_len = jnp.asarray(kv_offset) + Skv
        if kv_positions is not None:
            kv_positions = jnp.pad(kv_positions, (0, pad),
                                   constant_values=-1)
    qg = q.reshape(B, Sq, Hkv, G, D)
    qpos = jnp.asarray(q_offset) + jnp.arange(Sq)
    window = jnp.asarray(window, jnp.int32)
    kv_len_arr = None if kv_len is None else jnp.asarray(kv_len)
    pos_chunks = (None if kv_positions is None
                  else kv_positions.reshape(n_chunks, chunk))

    kc = k.reshape(B, n_chunks, chunk, Hkv, D)
    vc = v.reshape(B, n_chunks, chunk, Hkv, D)

    def body(carry, inputs):
        out, m, lsum = carry
        ci, kci, vci = inputs[:3]
        if pos_chunks is not None:
            kpos = inputs[3]
            kvalid = kpos >= 0
        else:
            kpos = jnp.asarray(kv_offset) + ci * chunk + jnp.arange(chunk)
            kvalid = None
        s = jnp.einsum("bqhgd,bchd->bqhgc", qg, kci,
                       preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        mask = _chunk_mask(qpos, kpos, causal=causal, window=window,
                           kv_len=kv_len_arr, kpos_valid=kvalid)  # (Sq, C)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))      # (B,Sq,Hkv,G)
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = lsum * alpha + jnp.sum(p, axis=-1)
        # probabilities enter the PV matmul in V's dtype (f32 accumulate),
        # as in the Pallas decode kernel: both paths then feed the MXU
        # the same operands whatever the default matmul precision
        pv = jnp.einsum("bqhgc,bchd->bqhgd", p.astype(vci.dtype), vci,
                        preferred_element_type=jnp.float32)
        out_new = out * alpha[..., None] + pv
        return (out_new, m_new, l_new), None

    out0 = jnp.zeros((B, Sq, Hkv, G, D), jnp.float32)
    m0 = jnp.full((B, Sq, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, Hkv, G), jnp.float32)
    xs = [jnp.arange(n_chunks), jnp.moveaxis(kc, 1, 0),
          jnp.moveaxis(vc, 1, 0)]
    if pos_chunks is not None:
        xs.append(pos_chunks)
    (out, m, lsum), _ = jax.lax.scan(body, (out0, m0, l0), tuple(xs))

    out = out.reshape(B, Sq, Hq, D)
    m = m.reshape(B, Sq, Hq)
    lsum = lsum.reshape(B, Sq, Hq)
    if return_partial:
        return AttnPartial(out=out, m=m, lsum=lsum)
    safe_l = jnp.where(lsum > 0, lsum, 1.0)
    return (out / safe_l[..., None]).astype(q.dtype)


def combine_partials(p: AttnPartial, axis_name: str,
                     out_dtype: Any) -> jax.Array:
    """Merge per-shard blockwise partials across a mesh axis (the
    sequence-sharded flash-decoding Gather)."""
    m_glob = jax.lax.pmax(p.m, axis_name)
    w = jnp.exp(p.m - m_glob)
    num = jax.lax.psum(p.out * w[..., None], axis_name)
    den = jax.lax.psum(p.lsum * w, axis_name)
    den = jnp.where(den > 0, den, 1.0)
    return (num / den[..., None]).astype(out_dtype)


def attention_reference(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        q_offset: int = 0, kv_len: Optional[int] = None,
                        softcap: float = 0.0,
                        scale: Optional[float] = None) -> jax.Array:
    """O(S²) dense oracle for tests."""
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    qg = q.reshape(B, Sq, Hkv, G, D)
    s = jnp.einsum("bqhgd,bkhd->bqhgk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    qpos = q_offset + jnp.arange(Sq)
    kpos = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kpos[None] <= qpos[:, None]
    if window > 0:
        mask &= kpos[None] > qpos[:, None] - window
    if kv_len is not None:
        mask &= kpos[None] < kv_len
    s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqhgk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, Hq, D).astype(q.dtype)
