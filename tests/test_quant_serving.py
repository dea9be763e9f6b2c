"""Quantized serving path (PR: Q4_0 weights + int8 KV pages).

Format level (fast lane): Q4_0 round-trip error bounds, the exact
pad-to-block path, per-layer stacked quantization, int8 KV row
round-trips, and the ``KVPoolConfig`` byte math (int8 pages must fit
>= 1.9x in the same pool bytes — ``docs/quantization.md``).

Dispatch level (fast lane): ``quantize_serving_params`` leaf
selection on the real bench-tiny tree, the ``qmm`` hook vs dense
parity, Pallas-kernel-vs-jnp-reference parity across tile shapes,
the int8 paged cache structure, and scale-aware paged decode
attention vs explicitly dequantized pools.

TP level (fast lane): the sharding specs map ``q4_packed`` /
``q4_scales`` by their parent weight's rule and ``k_scale`` /
``v_scale`` like the code buffers, and column-sharding commutes with
quantization (Q4_0 quantizes along K; the head split slices N).

Engine level: a fast q4+int8 run through ``ContinuousServingEngine``
(page_bytes accounting, dispatch counters, prefix-sharing parity over
int8 pages), and the ``slow``-marked e2e divergence gate — the fp32
engine's greedy continuations replayed teacher-forced through the
quantized engine must match at or above the documented bound
(``benchmarks.serving_bench.QUANT_MATCH_BOUND``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import ModelConfig, build_model
from repro.quant import kv_int8, q4_0
from repro.quant.policy import (QuantPolicy, count_q4_leaves, is_q4_leaf,
                                make_qmm, quantize_serving_params)
from repro.serving import (ContinuousServingEngine, KVPoolConfig, Request,
                           SamplingParams)

QUANT_MATCH_BOUND = 0.80    # documented bound, docs/quantization.md


def tiny_cfg(**kw):
    base = dict(name="bench-tiny", arch_type="dense", n_layers=4,
                d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
                vocab_size=259, dtype=jnp.float32)
    base.update(kw)
    return ModelConfig(**base)


# ----------------------------------------------------------------------
# Q4_0 format
# ----------------------------------------------------------------------

class TestQ4Format:
    def test_round_trip_error_bounded_by_half_scale(self):
        w = jax.random.normal(jax.random.PRNGKey(0), (96, 8), jnp.float32)
        packed, scales = q4_0.quantize(w)
        wd = q4_0.dequantize(packed, scales)
        # the code grid is asymmetric (-8..+7 times the scale), so the
        # clamped positive side can err by up to one full |scale|
        # (plus the fp16 round-trip of the scale itself)
        bound = jnp.repeat(jnp.abs(scales), q4_0.BLOCK, axis=0) + 1e-6
        assert jnp.all(jnp.abs(wd - w) <= bound)

    def test_block_absmax_is_exact(self):
        # the signed max of each block maps to code 0 or 15 exactly
        # (scale = signed_max / -8), modulo the fp16 scale round-trip
        w = jax.random.normal(jax.random.PRNGKey(1), (64, 4), jnp.float32)
        packed, scales = q4_0.quantize(w)
        wd = q4_0.dequantize(packed, scales)
        wf = np.asarray(w).reshape(-1, q4_0.BLOCK, 4)
        wdf = np.asarray(wd).reshape(-1, q4_0.BLOCK, 4)
        i = np.argmax(np.abs(wf), axis=1)
        got = np.take_along_axis(wdf, i[:, None, :], axis=1)[:, 0, :]
        want = np.take_along_axis(wf, i[:, None, :], axis=1)[:, 0, :]
        assert np.allclose(got, want, rtol=1e-3, atol=1e-6)

    def test_unaligned_k_raises_without_pad(self):
        w = jnp.ones((33, 4), jnp.float32)
        with pytest.raises(ValueError, match="pad=True"):
            q4_0.quantize(w)

    def test_pad_to_block_is_exact(self):
        # zero rows quantize to code 8 -> dequantize to exactly 0.0,
        # so the padded product equals the unpadded product bit-for-bit
        K = 40                                     # pads to 64
        w = jax.random.normal(jax.random.PRNGKey(2), (K, 8), jnp.float32)
        packed, scales = q4_0.quantize(w, pad=True)
        assert packed.shape == (q4_0.padded_k(K) // 2, 8)
        wd = q4_0.dequantize(packed, scales)
        assert jnp.all(wd[K:] == 0.0)
        x = jax.random.normal(jax.random.PRNGKey(3), (5, K), jnp.float32)
        xp = jnp.pad(x, ((0, 0), (0, q4_0.padded_k(K) - K)))
        assert jnp.array_equal(x @ wd[:K], xp @ wd)

    def test_quantize_stacked_matches_per_layer(self):
        w = jax.random.normal(jax.random.PRNGKey(4), (3, 64, 8),
                              jnp.float32)
        ps, ss = q4_0.quantize_stacked(w)
        for i in range(3):
            p, s = q4_0.quantize(w[i])
            assert jnp.array_equal(ps[i], p)
            assert jnp.array_equal(ss[i], s)

    def test_bytes_per_weight(self):
        assert q4_0.BYTES_PER_WEIGHT == 0.5625
        assert q4_0.quantized_bytes((64, 16)) == 64 * 16 // 2 + 2 * 16 * 4


# ----------------------------------------------------------------------
# int8 KV rows
# ----------------------------------------------------------------------

class TestKvInt8:
    def test_round_trip_error_bounded_by_half_scale(self):
        x = jax.random.normal(jax.random.PRNGKey(5), (6, 2, 32),
                              jnp.float32)
        q, s = kv_int8.quantize_rows(x)
        assert q.dtype == jnp.int8 and s.shape == (6, 2)
        xd = kv_int8.dequantize_rows(q, s)
        assert jnp.all(jnp.abs(xd - x) <= s[..., None] * 0.5 + 1e-7)

    def test_zero_rows_round_trip_exactly(self):
        x = jnp.zeros((3, 2, 16), jnp.float32)
        q, s = kv_int8.quantize_rows(x)
        assert jnp.all(q == 0) and jnp.all(s == 0)
        assert jnp.array_equal(kv_int8.dequantize_rows(q, s), x)

    def test_bytes_per_row_head(self):
        assert kv_int8.kv_bytes_per_row_head(32) == 36      # vs 128 fp32


# ----------------------------------------------------------------------
# pool byte math
# ----------------------------------------------------------------------

class TestPoolByteMath:
    def _cfg(self, kv_dtype, head_dim=32):
        return KVPoolConfig(n_pages=8, page_size=16, n_layers=4,
                            n_kv_heads=2, head_dim=head_dim,
                            dtype_bytes=4, kv_dtype=kv_dtype)

    def test_fp32_page_bytes(self):
        assert self._cfg("fp32").page_bytes == 2 * 4 * 16 * 2 * 32 * 4

    def test_int8_page_bytes(self):
        assert self._cfg("int8").page_bytes == 2 * 4 * 16 * 2 * (32 + 4)

    @pytest.mark.parametrize("head_dim", (32, 64, 128))
    def test_capacity_ratio_clears_floor(self, head_dim):
        # 4D/(D+4): 3.56x at 32, asymptotically 4x — floor is 1.9x
        ratio = (self._cfg("fp32", head_dim).page_bytes
                 / self._cfg("int8", head_dim).page_bytes)
        assert ratio >= 1.9
        assert ratio == pytest.approx(4 * head_dim / (head_dim + 4))

    def test_unknown_kv_dtype_raises(self):
        with pytest.raises(ValueError, match="kv_dtype"):
            self._cfg("fp8").page_bytes


# ----------------------------------------------------------------------
# policy: leaf selection + the qmm hook
# ----------------------------------------------------------------------

class TestQuantizeServingParams:
    def test_selects_attn_and_mlp_projections(self):
        model = build_model(tiny_cfg())
        params = model.init(jax.random.PRNGKey(0))
        qp = quantize_serving_params(params)
        # the uniform stack: w_q/w_k/w_v/w_o + w_gate/w_up/w_down
        assert count_q4_leaves(qp) == 7
        lp = qp["layers"]
        assert is_q4_leaf(lp["attn"]["w_q"])
        assert not is_q4_leaf(qp["embed"])
        # stacked (L, K, N) leaves quantize per layer along K
        L, d = 4, 128
        assert lp["attn"]["w_q"]["q4_packed"].shape == (L, d // 2, d)
        assert lp["attn"]["w_q"]["q4_scales"].shape == (L, d // 32, d)

    def test_min_size_spares_small_leaves(self):
        model = build_model(tiny_cfg())
        params = model.init(jax.random.PRNGKey(0))
        assert count_q4_leaves(
            quantize_serving_params(params, min_size=10**9)) == 0

    def test_policy_validates(self):
        with pytest.raises(ValueError, match="weights"):
            QuantPolicy(weights="q8")
        with pytest.raises(ValueError, match="kv_dtype"):
            QuantPolicy(kv_dtype="fp16")
        assert not QuantPolicy().active
        assert QuantPolicy(kv_dtype="int8").active


class TestQmmHook:
    def test_dense_leaf_passthrough(self):
        qmm = make_qmm("ref")
        x = jax.random.normal(jax.random.PRNGKey(6), (3, 8), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(7), (8, 5), jnp.float32)
        assert jnp.array_equal(qmm(x, w), x @ w)

    def test_q4_leaf_matches_dequantized_dense(self):
        K, N = 96, 64
        w = jax.random.normal(jax.random.PRNGKey(8), (K, N), jnp.float32)
        packed, scales = q4_0.quantize(w)
        leaf = {"q4_packed": packed, "q4_scales": scales}
        x = jax.random.normal(jax.random.PRNGKey(9), (2, 3, K),
                              jnp.float32)
        got = make_qmm("ref")(x, leaf)
        want = x @ q4_0.dequantize(packed, scales)
        assert got.shape == (2, 3, N)
        assert jnp.allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_pad_to_block_activation_padding(self):
        K, N = 40, 32                              # K pads to 64
        w = jax.random.normal(jax.random.PRNGKey(10), (K, N), jnp.float32)
        packed, scales = q4_0.quantize(w, pad=True)
        x = jax.random.normal(jax.random.PRNGKey(11), (4, K), jnp.float32)
        got = make_qmm("ref")(x, {"q4_packed": packed,
                                  "q4_scales": scales})
        want = x @ q4_0.dequantize(packed, scales)[:K]
        assert jnp.allclose(got, want, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("shape,blocks", [
        ((64, 64), (32, 32)),
        ((128, 96), (64, 32)),
        ((96, 128), (32, 128)),
    ])
    def test_kernel_matches_reference(self, shape, blocks):
        from repro.kernels.ops import q4_matmul
        K, N = shape
        bk, bn = blocks
        w = jax.random.normal(jax.random.PRNGKey(12), (K, N), jnp.float32)
        packed, scales = q4_0.quantize(w)
        x = jax.random.normal(jax.random.PRNGKey(13), (3, K), jnp.float32)
        ref = q4_matmul(x, packed, scales, impl="ref")
        ker = q4_matmul(x, packed, scales, impl="kernel",
                        block_k=bk, block_n=bn)
        assert jnp.allclose(ker, ref, rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------------------
# int8 paged cache + attention read path
# ----------------------------------------------------------------------

class TestInt8PagedCache:
    def test_cache_structure(self):
        model = build_model(tiny_cfg())
        cache = model.init_cache(2, 64, page_size=8, n_pages=16,
                                 kv_dtype="int8")
        lc = cache["layers"][0]["self"]
        assert lc["k"].dtype == jnp.int8
        assert lc["k"].shape == (16 * 8, 2 * 32)
        assert lc["k_scale"].dtype == jnp.float32
        assert lc["k_scale"].shape == (16 * 8, 2)
        fp = model.init_cache(2, 64, page_size=8, n_pages=16)
        assert "k_scale" not in fp["layers"][0]["self"]

    def test_int8_requires_paged_cache(self):
        model = build_model(tiny_cfg())
        with pytest.raises(ValueError, match="kv_dtype"):
            model.init_cache(2, 64, kv_dtype="int8")
        with pytest.raises(ValueError, match="kv_dtype"):
            model.init_cache(2, 64, page_size=8, n_pages=16,
                             kv_dtype="fp8")

    def test_scaled_ref_matches_dequantized_pool(self):
        from repro.kernels.ref import paged_decode_attention_ref
        P, ps, H, G, D, B = 6, 4, 2, 2, 16, 3
        key = jax.random.PRNGKey(14)
        kv = jax.random.normal(key, (P, ps, H, D), jnp.float32)
        q8, s = kv_int8.quantize_rows(kv)
        q = jax.random.normal(jax.random.PRNGKey(15), (B, H, G, D),
                              jnp.float32)
        bt = jnp.asarray([[1, 2, 0], [3, 4, 5], [2, 0, 0]], jnp.int32)
        lens = jnp.asarray([6, 10, 3], jnp.int32)
        deq = kv_int8.dequantize_rows(q8, s)
        want = paged_decode_attention_ref(q, deq, deq, bt, lens)
        got = paged_decode_attention_ref(q, q8, q8, bt, lens,
                                         k_scales=s, v_scales=s)
        assert jnp.allclose(got, want, rtol=1e-5, atol=1e-6)


# ----------------------------------------------------------------------
# TP sharding of quantized leaves
# ----------------------------------------------------------------------

class TestTpSpecs:
    def test_q4_leaves_shard_by_parent_rule(self):
        from jax.sharding import PartitionSpec as P

        from repro.launch.shardings import serving_tp_param_specs
        model = build_model(tiny_cfg())
        qp = quantize_serving_params(model.init(jax.random.PRNGKey(0)))
        shapes = jax.eval_shape(lambda: qp)
        specs = serving_tp_param_specs(shapes, axis="model")
        attn, mlp = specs["layers"]["attn"], specs["layers"]["mlp"]
        # head-sharded parents: packed AND scales slice their N dim
        assert attn["w_q"]["q4_packed"] == P(None, None, "model")
        assert attn["w_q"]["q4_scales"] == P(None, None, "model")
        # replicated parents stay replicated when quantized
        assert attn["w_o"]["q4_packed"] == P()
        assert mlp["w_down"]["q4_scales"] == P()

    def test_scale_buffers_shard_like_code_buffers(self):
        from jax.sharding import PartitionSpec as P

        from repro.launch.shardings import paged_cache_specs
        model = build_model(tiny_cfg())
        cache = model.init_cache(2, 64, page_size=8, n_pages=16,
                                 kv_dtype="int8")
        specs = paged_cache_specs(jax.eval_shape(lambda: cache),
                                  axis="model")
        lc = specs["layers"][0]["self"]
        assert lc["k"] == P(None, "model")
        assert lc["k_scale"] == P(None, "model")
        assert lc["v_scale"] == P(None, "model")

    def test_column_shard_commutes_with_quantize(self):
        # Q4_0 quantizes along K; the head split slices columns (N),
        # so shard-then-quantize == quantize-then-shard byte-for-byte
        w = jax.random.normal(jax.random.PRNGKey(16), (64, 32),
                              jnp.float32)
        packed, scales = q4_0.quantize(w)
        for cols in (slice(0, 16), slice(16, 32)):
            p, s = q4_0.quantize(w[:, cols])
            assert jnp.array_equal(packed[:, cols], p)
            assert jnp.array_equal(scales[:, cols], s)


# ----------------------------------------------------------------------
# engines
# ----------------------------------------------------------------------

def _reqs(n=3, max_new=6, seed=21):
    rng = np.random.default_rng(seed)
    return [Request(uid=i, prompt=list(rng.integers(1, 258, 6 + 4 * i)),
                    sampling=SamplingParams(max_new_tokens=max_new))
            for i in range(n)]


class TestQuantEngine:
    def test_q4_int8_engine_serves_and_accounts(self):
        model = build_model(tiny_cfg())
        params = model.init(jax.random.PRNGKey(0))
        qp = QuantPolicy(weights="q4", kv_dtype="int8", impl="ref")
        eng = ContinuousServingEngine(model, params, max_len=48,
                                      max_running=4, page_size=8,
                                      quant=qp)
        # the runner rewrote its params copy; the shared model is clean
        assert count_q4_leaves(eng.core.runner.params) == 7
        assert count_q4_leaves(params) == 0
        assert eng.pool.cfg.page_bytes == 2 * 4 * 8 * 2 * (32 + 4)
        comps = eng.generate(_reqs())
        assert [len(c.tokens) for c in comps] == [6, 6, 6]
        reg = eng.core.registry
        disp = reg.get("runner.quant.q4_dispatch")
        assert disp is not None
        assert disp.value(phase="prefill") > 0
        assert disp.value(phase="decode") > 0

    def test_prefix_sharing_parity_over_int8_pages(self):
        # shared int8 pages (+ CoW of codes AND scales) must not change
        # a single greedy token vs the same engine without the cache
        model = build_model(tiny_cfg())
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(31)
        system = list(rng.integers(1, 258, 24))     # 3 full pages @ ps=8
        reqs = [Request(uid=i,
                        prompt=system + list(rng.integers(1, 258, 4)),
                        sampling=SamplingParams(max_new_tokens=6))
                for i in range(4)]
        qp = QuantPolicy(kv_dtype="int8")
        toks = {}
        for cached in (False, True):
            # max_running=1 serves sequentially, so request 0's pages
            # are published before request 1 admits and can share them
            eng = ContinuousServingEngine(model, params, max_len=64,
                                          max_running=1, page_size=8,
                                          prefix_cache=cached, quant=qp)
            toks[cached] = [c.tokens for c in eng.generate(reqs)]
        assert toks[True] == toks[False]
        assert eng.pool.stats["shared_pages"] > 0

    def test_int8_only_greedy_matches_fp32_on_short_decode(self):
        # int8 KV error at these context lengths is far below bench-tiny
        # argmax margins for a couple of steps; parity here is a cheap
        # canary for the read/write paths (the real accuracy gate is the
        # slow teacher-forced test below)
        model = build_model(tiny_cfg())
        params = model.init(jax.random.PRNGKey(0))
        reqs = _reqs(n=2, max_new=2, seed=41)
        toks = {}
        for name, qp in (("fp32", None),
                         ("int8", QuantPolicy(kv_dtype="int8"))):
            eng = ContinuousServingEngine(model, params, max_len=48,
                                          max_running=4, page_size=8,
                                          quant=qp)
            toks[name] = [c.tokens for c in eng.generate(reqs)]
        assert toks["int8"] == toks["fp32"]


@pytest.mark.slow
class TestDivergenceGate:
    def test_teacher_forced_match_meets_documented_bound(self):
        """The e2e divergence gate (docs/quantization.md): fp32 greedy
        continuations replayed teacher-forced through the q4+int8
        engine must agree on >= QUANT_MATCH_BOUND of positions.  The
        model is briefly warm-trained (fixed seed, deterministic) so
        argmax margins are real; teacher forcing makes the rate
        cascade-free."""
        from repro.data.pipeline import PackedLMDataset
        from repro.training.loop import train
        from repro.training.optimizer import AdamWConfig

        model = build_model(tiny_cfg())
        params0 = model.init(jax.random.PRNGKey(0))
        ds = PackedLMDataset(seq_len=64, n_docs=500, vocab_size=259)
        params, _, _ = train(model, params0, ds.batches(8),
                             AdamWConfig(lr=2e-3, warmup_steps=5,
                                         total_steps=80),
                             steps=80, log_every=1000)

        rng = np.random.default_rng(7)
        prompts = [list(rng.integers(1, 258, 4 + 4 * (i % 3)))
                   for i in range(4)]
        gen = SamplingParams(temperature=0.0, max_new_tokens=12)
        one = SamplingParams(temperature=0.0, max_new_tokens=1)

        def engine(quant):
            return ContinuousServingEngine(model, params, max_len=64,
                                           max_running=8, page_size=8,
                                           quant=quant)

        ref = {c.uid: c.tokens for c in engine(None).generate(
            [Request(uid=i, prompt=p, sampling=gen)
             for i, p in enumerate(prompts)])}
        replay, want = [], []
        for i, p in enumerate(prompts):
            for j in range(len(ref[i])):
                replay.append(Request(uid=len(replay),
                                      prompt=p + ref[i][:j],
                                      sampling=one))
                want.append(ref[i][j])
        qeng = engine(QuantPolicy(weights="q4", kv_dtype="int8",
                                  impl="ref"))
        got = {c.uid: c.tokens for c in qeng.generate(replay)}
        match = sum(int(got[u][0] == want[u]) for u in range(len(want)))
        rate = match / len(want)
        assert rate >= QUANT_MATCH_BOUND, (
            f"teacher-forced greedy match {rate:.3f} under the "
            f"documented bound {QUANT_MATCH_BOUND}")
