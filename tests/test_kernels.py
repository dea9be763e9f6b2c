"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (deliverable c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.ops import gqa_decode_attention
from repro.kernels.q4_gemm import q4_gemm
from repro.quant.q4_0 import BLOCK, dequantize, quantize, quantized_bytes


def _rand(shape, seed, dtype=np.float32, scale=1.0):
    return (np.random.default_rng(seed).normal(size=shape) * scale
            ).astype(dtype)


class TestQ4Gemm:
    @pytest.mark.parametrize("M,K,N,bn,bk", [
        (1, 256, 512, 256, 256),      # decode GEMV
        (4, 512, 256, 128, 128),
        (8, 1024, 768, 256, 256),
        (3, 64, 128, 128, 64),        # small / non-square
        (16, 128, 384, 128, 32),      # bk == BLOCK
        (2, 320, 128, 64, 160),       # odd-ish tiling
    ])
    def test_matches_oracle(self, M, K, N, bn, bk):
        w = _rand((K, N), 0, scale=0.2)
        x = _rand((M, K), 1)
        p, s = quantize(w)
        out = q4_gemm(jnp.asarray(x), p, s, block_n=bn, block_k=bk,
                      interpret=True)
        want = ref.q4_gemm_ref(jnp.asarray(x), p, s)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("xdtype", [np.float32, jnp.bfloat16])
    def test_dtypes(self, xdtype):
        w = _rand((128, 128), 0, scale=0.2)
        x = jnp.asarray(_rand((2, 128), 1)).astype(xdtype)
        p, s = quantize(w)
        out = q4_gemm(x, p, s, block_n=128, block_k=128, interpret=True)
        want = ref.q4_gemm_ref(x, p, s)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-2, atol=2e-2)

    def test_rejects_bad_tiling(self):
        w = _rand((128, 100), 0)
        p, s = quantize(w)
        with pytest.raises(ValueError):
            q4_gemm(jnp.zeros((1, 128)), p, s, block_n=64, block_k=128)


class TestDecodeAttention:
    @pytest.mark.parametrize("B,S,H,G,D,bs", [
        (2, 256, 2, 4, 64, 64),
        (1, 512, 4, 1, 128, 128),
        (3, 128, 2, 8, 32, 32),
        (1, 1024, 1, 4, 256, 256),    # gemma3-like MQA
    ])
    @pytest.mark.parametrize("fill", [0.3, 1.0])
    def test_matches_oracle(self, B, S, H, G, D, bs, fill):
        kv_len = max(1, int(S * fill))
        q = _rand((B, H, G, D), 0)
        k = _rand((B, S, H, D), 1)
        v = _rand((B, S, H, D), 2)
        out = decode_attention(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v), kv_len, scale=D ** -0.5,
                               block_s=bs, interpret=True)
        want = ref.decode_attention_ref(jnp.asarray(q), jnp.asarray(k),
                                        jnp.asarray(v), kv_len)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_ops_wrapper_contract(self):
        """gqa_decode_attention matches the model-zoo flash decode."""
        from repro.models.attention import flash_attention
        B, S, Hq, Hkv, D = 2, 64, 8, 2, 32
        kv_len = 40
        q = _rand((B, 1, Hq, D), 0)
        k = np.zeros((B, S, Hkv, D), np.float32)
        v = np.zeros((B, S, Hkv, D), np.float32)
        k[:, :kv_len] = _rand((B, kv_len, Hkv, D), 1)
        v[:, :kv_len] = _rand((B, kv_len, Hkv, D), 2)
        out = gqa_decode_attention(jnp.asarray(q), jnp.asarray(k),
                                   jnp.asarray(v), kv_len)
        want = flash_attention(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v), causal=True,
                               q_offset=kv_len - 1, kv_len=kv_len, chunk=16)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=1e-4, atol=1e-5)


class TestQ4Quant:
    @given(k_blocks=st.integers(1, 8), n=st.integers(1, 64),
           scale=st.floats(0.01, 100.0))
    @settings(max_examples=40, deadline=None)
    @pytest.mark.slow
    def test_roundtrip_error_bound(self, k_blocks, n, scale):
        """|dequant(quant(w)) - w| <= |block scale| (+ fp16 rounding)."""
        K = k_blocks * BLOCK
        w = _rand((K, n), k_blocks * 100 + n, scale=scale)
        p, s = quantize(w)
        wd = np.asarray(dequantize(p, s))
        err = np.abs(wd - w)
        bound = np.abs(np.asarray(s)).repeat(BLOCK, axis=0)
        assert np.all(err <= bound * 1.02 + 1e-6)

    @given(k_blocks=st.integers(1, 4), n=st.integers(1, 32))
    @settings(max_examples=20, deadline=None)
    @pytest.mark.slow
    def test_idempotent(self, k_blocks, n):
        """Quantizing an already-quantized weight is exact."""
        K = k_blocks * BLOCK
        w = _rand((K, n), 7)
        p, s = quantize(w)
        wd = dequantize(p, s)
        p2, s2 = quantize(wd)
        np.testing.assert_allclose(np.asarray(dequantize(p2, s2)),
                                   np.asarray(wd), rtol=1e-6, atol=1e-7)

    def test_bytes_accounting(self):
        assert quantized_bytes((256, 100)) == 256 * 100 // 2 + 8 * 100 * 4

    def test_zero_block(self):
        w = np.zeros((BLOCK, 3), np.float32)
        p, s = quantize(w)
        assert np.asarray(dequantize(p, s)).sum() == 0.0


class TestRGLRUScanKernel:
    @pytest.mark.parametrize("B,T,W,bt", [
        (2, 37, 16, 8),      # padded tail chunk
        (1, 128, 64, 128),   # single chunk
        (3, 64, 32, 16),
        (2, 200, 8, 64),
    ])
    @pytest.mark.parametrize("with_h0", [False, True])
    def test_matches_oracle(self, B, T, W, bt, with_h0):
        from repro.kernels.rglru_scan import rglru_scan_kernel
        rng = np.random.default_rng(B * T + W)
        a = jnp.asarray(rng.uniform(0.7, 0.999, (B, T, W)), jnp.float32)
        u = jnp.asarray(rng.normal(size=(B, T, W)) * 0.3, jnp.float32)
        h0 = (jnp.asarray(rng.normal(size=(B, W)), jnp.float32)
              if with_h0 else None)
        out = rglru_scan_kernel(a, u, h0=h0, block_t=bt, interpret=True)
        want = ref.rglru_scan_ref(a, u, h0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_matches_model_zoo_recurrence(self):
        """Kernel == repro.models.recurrent gate semantics."""
        from repro.kernels.rglru_scan import rglru_scan_kernel
        from repro.models.recurrent import (_gates, init_rglru_block,
                                            rglru_scan)
        p = init_rglru_block(jax.random.PRNGKey(0), 16, 24, 4, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 19, 24))
        a, u = _gates(p, x)
        want, _ = rglru_scan(p, x)
        out = rglru_scan_kernel(a, u, block_t=8, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(want, np.float32),
                                   rtol=1e-5, atol=1e-5)

    def test_ops_wrapper(self):
        from repro.kernels.ops import rglru_linear_scan
        rng = np.random.default_rng(0)
        a = jnp.asarray(rng.uniform(0.5, 0.99, (1, 10, 4)), jnp.float32)
        u = jnp.asarray(rng.normal(size=(1, 10, 4)), jnp.float32)
        out = rglru_linear_scan(a, u, impl="ref")
        want = ref.rglru_scan_ref(a, u)
        # jit-fused associative scan reorders the products slightly
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)
