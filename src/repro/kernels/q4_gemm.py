"""Pallas TPU kernel: Q4_0 dequant + matmul (the decode GEMV hot-spot).

The paper's engine spends its decode time in Q4_0 GEMV/GEMM NEON
kernels (§2.7, §4).  The TPU adaptation rethinks the blocking for the
memory hierarchy: weight tiles stream HBM→VMEM in their *packed* form
(0.5625 B/weight — the whole point of Q4_0 is bandwidth), are unpacked
and dequantized in VMEM registers, and feed the MXU as bf16/f32 tiles
with 128-aligned shapes.  fp32 accumulation across the K grid axis.

Layout (see ``repro.quant.q4_0``):
    x       (M, K)        activation
    packed  (K//2, N)     two 4-bit codes per byte along K
    scales  (K//32, N)    per-block scale

Grid: (N/BN, K/BK); the K axis accumulates into the output block
(revisited across the innermost grid dim).  BK is a multiple of 32 so
scale blocks never straddle tiles.  M stays whole per tile — decode is
M ∈ {1..batch}, far below the 128 sublane budget at these sizes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..quant.q4_0 import BLOCK


DEFAULT_BN = 256
DEFAULT_BK = 256


def _q4_gemm_kernel(x_ref, packed_ref, scales_ref, out_ref, *, n_k: int):
    """One (BN, BK) tile: unpack, dequant, matmul, accumulate."""
    k = pl.program_id(1)

    # widen before unpacking: Mosaic does not legalize 8-bit integer
    # arithmetic, so the nibble maths runs on int32 lanes
    packed = packed_ref[...].astype(jnp.int32)     # (BK//2, BN)
    lo = (packed & 0x0F) - 8
    hi = (packed >> 4) - 8
    codes = jnp.stack([lo, hi], axis=1)            # (BK//2, 2, BN)
    bk2, _, bn = codes.shape
    codes = codes.reshape(2 * bk2, bn)             # (BK, BN)

    scales = scales_ref[...]                       # (BK//32, BN)
    w = codes.astype(jnp.float32) * jnp.repeat(scales, BLOCK, axis=0)

    x = x_ref[...].astype(jnp.float32)             # (M, BK)
    acc = jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = acc

    @pl.when(k > 0)
    def _accum():
        out_ref[...] += acc


def q4_gemm(x: jax.Array, packed: jax.Array, scales: jax.Array, *,
            block_n: int = DEFAULT_BN, block_k: int = DEFAULT_BK,
            interpret: bool = True) -> jax.Array:
    """x (M, K) @ dequant(packed, scales) (K, N) -> (M, N) f32.

    ``interpret=True`` executes the kernel body on CPU (this container's
    validation mode); on TPU pass ``interpret=False``.
    """
    M, K = x.shape
    K2, N = packed.shape
    if K != 2 * K2:
        raise ValueError(f"K mismatch: x has {K}, packed has {2 * K2}")
    if block_k % BLOCK:
        raise ValueError(f"block_k={block_k} must be a multiple of {BLOCK}")
    block_n = min(block_n, N)
    block_k = min(block_k, K)
    if N % block_n or K % block_k:
        raise ValueError(f"(K={K}, N={N}) not divisible by "
                         f"(block_k={block_k}, block_n={block_n})")
    n_n, n_k = N // block_n, K // block_k

    return pl.pallas_call(
        functools.partial(_q4_gemm_kernel, n_k=n_k),
        grid=(n_n, n_k),
        in_specs=[
            pl.BlockSpec((M, block_k), lambda n, k: (0, k)),
            pl.BlockSpec((block_k // 2, block_n), lambda n, k: (k, n)),
            pl.BlockSpec((block_k // BLOCK, block_n), lambda n, k: (k, n)),
        ],
        out_specs=pl.BlockSpec((M, block_n), lambda n, k: (0, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(x, packed, scales)
