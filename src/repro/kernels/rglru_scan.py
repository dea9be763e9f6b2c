"""Pallas TPU kernel: RG-LRU linear-recurrence scan.

The sequential hot-spot of the hybrid archs (recurrentgemma): given
per-step gates ``a`` and scaled inputs ``u`` (both (B, T, W), computed
by cheap GEMMs outside), produce

    h_t = a_t ⊙ h_{t-1} + u_t          (elementwise, W-wide)

TPU adaptation: the recurrence is memory-bound (3 streams of B·T·W) and
strictly sequential in T, so the kernel tiles T into VMEM-resident
chunks — grid (T/BT,) — and carries the running state h (B, W) in VMEM
scratch across grid steps.  Inside a chunk a ``fori_loop`` walks rows
at VREG speed; HBM sees exactly one read of a/u and one write of h per
element.  W shards over the mesh's model axis outside the kernel (the
recurrence is elementwise in W — ArcLight's row-partitioning applied to
the recurrence width, DESIGN.md §4).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_scan_kernel(a_ref, u_ref, o_ref, h_ref, *, block_t: int):
    ti = pl.program_id(0)

    @pl.when(ti == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def body(i, h):
        h = a_ref[:, i, :] * h + u_ref[:, i, :]
        o_ref[:, pl.ds(i, 1), :] = h[:, None, :].astype(o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, block_t, body, h_ref[...])


def rglru_scan_kernel(a: jax.Array, u: jax.Array, *,
                      h0: Optional[jax.Array] = None,
                      block_t: int = 128,
                      interpret: bool = True) -> jax.Array:
    """h[t] = a[t]*h[t-1] + u[t] over axis 1.  a,u (B,T,W) -> h (B,T,W).

    ``h0``: optional initial state (B, W) — folded into the first step
    (h_1 = a_1·h0 + u_1), matching ``repro.models.recurrent``.
    """
    B, T, W = a.shape
    if u.shape != a.shape:
        raise ValueError(f"shape mismatch {a.shape} vs {u.shape}")
    if h0 is not None:
        u = u.at[:, 0].add(a[:, 0] * h0)
    block_t = min(block_t, T)
    pad = (-T) % block_t
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
        u = jnp.pad(u, ((0, 0), (0, pad), (0, 0)))
    n_t = (T + pad) // block_t

    out = pl.pallas_call(
        functools.partial(_rglru_scan_kernel, block_t=block_t),
        grid=(n_t,),
        in_specs=[
            pl.BlockSpec((B, block_t, W), lambda t: (0, t, 0)),
            pl.BlockSpec((B, block_t, W), lambda t: (0, t, 0)),
        ],
        out_specs=pl.BlockSpec((B, block_t, W), lambda t: (0, t, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T + pad, W), a.dtype),
        scratch_shapes=[pltpu.VMEM((B, W), jnp.float32)],
        interpret=interpret,
    )(a, u)
    return out[:, :T]
