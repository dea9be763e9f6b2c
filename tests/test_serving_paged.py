"""Paged KV pool, continuous-batching scheduler + engine (PR: serving).

Property tests: page aliasing, free-list reuse, fixed page ids, byte
accounting.
System tests: greedy token parity with the bucket engine (including
under forced preemption), late-arrival admission without recompiling
the decode step, paged Pallas kernel vs jnp oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.models import ModelConfig, build_model
from repro.serving import (ContinuousServingEngine, ContinuousScheduler,
                           KVCachePool, KVPoolConfig, Request,
                           SamplingParams, ServingEngine)


def _pool(n_pages=17, page_size=4):
    return KVCachePool(KVPoolConfig(
        n_pages=n_pages, page_size=page_size, n_layers=2, n_kv_heads=2,
        head_dim=8, dtype_bytes=4))


class TestKVPool:
    def test_scratch_page_never_allocated(self):
        pool = _pool(n_pages=5)
        for uid in range(4):
            assert pool.grow(uid, 4)
        assert pool.n_free() == 0
        for uid in range(4):
            assert 0 not in pool.block_table(uid)

    @given(ops=st.lists(st.integers(0, 9), min_size=1, max_size=60))
    @settings(max_examples=25, deadline=None)
    def test_pages_never_alias_across_live_sequences(self, ops):
        """Random grow/free interleavings: a physical page is owned by
        at most one live sequence, and ownership matches block tables."""
        pool = _pool(n_pages=13)
        lens = {}
        for i, op in enumerate(ops):
            uid = op % 3
            if op < 6:   # grow by 1..6 tokens
                want = lens.get(uid, 0) + 1 + op
                if pool.cfg.pages_for(want) <= pool.cfg.max_pages_per_seq:
                    if pool.grow(uid, want):
                        lens[uid] = want
            else:        # free
                pool.free(uid)
                lens.pop(uid, None)
            tables = {u: pool.block_table(u) for u in lens}
            seen = {}
            for u, pages in tables.items():
                assert len(pages) == pool.cfg.pages_for(lens[u])
                for p in pages:
                    assert p != 0, "scratch page leaked"
                    assert p not in seen, f"page {p} aliased {seen.get(p)}/{u}"
                    seen[p] = u
            assert len(seen) + pool.n_free() == pool.cfg.n_pages - 1

    def test_freed_pages_are_reused(self):
        pool = _pool(n_pages=9)
        assert pool.grow(0, 32)          # all 8 usable pages
        first = pool.block_table(0)
        pool.free(0)
        assert pool.grow(1, 32)
        assert sorted(pool.block_table(1)) == sorted(first)
        # LIFO: the most recently freed (cache-warm) page comes first
        assert pool.block_table(1)[0] == first[-1]

    def test_capacity_and_live_bytes_accounting(self):
        cfg = KVPoolConfig(n_pages=12, page_size=4, n_layers=3,
                           n_kv_heads=2, head_dim=8, dtype_bytes=4)
        pool = KVCachePool(cfg)
        assert pool.capacity_bytes_per_shard() == {0: 12 * cfg.page_bytes}
        assert pool.grow(0, 16)          # 4 pages
        assert pool.n_live() * cfg.page_bytes == 4 * cfg.page_bytes
        assert pool.n_live() + pool.n_free() == cfg.n_pages - 1

    def test_page_script_hands_out_fixed_ids(self):
        """The allocation order is part of the device contract: block
        tables, and so every program's page walk, follow these ids."""
        assert page_script() == PAGE_SCRIPT_IDS

    @pytest.mark.parametrize("n_shards", [1, 2, 4])
    def test_page_ids_do_not_depend_on_shard_count(self, n_shards):
        assert page_script(n_shards) == page_script(1)

    @pytest.mark.parametrize("n_shards", [1, 2, 4])
    def test_capacity_per_shard_is_its_head_slice_of_every_page(
            self, n_shards):
        cfg = KVPoolConfig(n_pages=9, page_size=4, n_layers=2,
                           n_kv_heads=4, head_dim=8, dtype_bytes=4,
                           n_shards=n_shards)
        assert KVCachePool(cfg).capacity_bytes_per_shard() == {
            s: 9 * cfg.page_shard_bytes for s in range(n_shards)}
        assert cfg.page_shard_bytes * n_shards == cfg.page_bytes


def page_script(n_shards=1):
    """A fixed grow / prefix-share / CoW / truncate / release /
    release_below / retained-eviction sequence on a fresh pool; returns
    the block tables and page copies it produced, in order."""
    pool = KVCachePool(KVPoolConfig(
        n_pages=13, page_size=4, n_layers=2, n_kv_heads=4, head_dim=8,
        dtype_bytes=4, n_shards=n_shards))
    out = []
    a = list(range(100, 110))
    assert pool.grow(0, len(a) + 1)
    pool.register_prefix(0, a)
    out.append(pool.block_table(0))
    b = a[:6] + [7, 7, 7, 7]             # diverges inside block 1: CoW
    assert pool.adopt_prefix(1, pool.match_prefix(b))
    assert pool.grow(1, len(b) + 1)
    out.append(pool.block_table(1))
    out.append(pool.drain_copies())
    assert pool.grow(2, 8)
    pool.share_pages(3, pool.block_table(2)[:1])
    assert pool.grow(3, 8)
    assert pool.ensure_writable(3, 1)    # its first page is shared: CoW
    out.append(pool.block_table(3))
    out.append(pool.drain_copies())
    pool.truncate_to(1, 5)
    pool.release(2)
    pool.release_below(3, 4)
    assert pool.grow(4, 12)              # LIFO: the pages just freed
    out.append(pool.block_table(4))
    pool.release(0)                      # its indexed page is retained
    assert pool.grow(5, 4 * pool.n_free())   # drains, then evicts it
    out.append(pool.block_table(5))
    return out


PAGE_SCRIPT_IDS = [[1, 2, 3], [1, 4, 5], [(2, 4)], [9, 8], [(6, 9)],
                   [9, 7, 6], [3, 5, 10, 11, 12, 2]]


class TestScheduler:
    def _sched(self, **kw):
        pool = _pool(**{k: v for k, v in kw.items()
                        if k in ("n_pages", "page_size")})
        return ContinuousScheduler(pool, max_running=kw.get("max_running", 2),
                                   max_len=kw.get("max_len", 64))

    def test_fcfs_admission_into_free_slots(self):
        s = self._sched(max_running=2)
        for i in range(3):
            s.submit(Request(uid=i, prompt=[1, 2, 3]), arrival=float(i))
        plan = s.step(now=10.0)
        assert [q.uid for q in plan.prefills] == [0, 1]
        assert len(s.waiting) == 1 and s.waiting[0].uid == 2

    def test_arrival_time_gates_admission(self):
        s = self._sched(max_running=2)
        s.submit(Request(uid=0, prompt=[1]), arrival=5.0)
        assert s.step(now=0.0).prefills == []
        assert [q.uid for q in s.step(now=6.0).prefills] == [0]

    def test_eviction_frees_slot_and_pages(self):
        s = self._sched(max_running=1)
        s.submit(Request(uid=0, prompt=[1, 2],
                         sampling=SamplingParams(max_new_tokens=1)))
        s.submit(Request(uid=1, prompt=[3, 4]))
        plan = s.step()
        assert [q.uid for q in plan.prefills] == [0]
        seq = plan.prefills[0]
        seq.n_prefilled = seq.prefill_target   # engine ran the prefill
        seq.generated.append(42)          # hits max_new_tokens
        plan = s.step()
        assert [q.uid for q in plan.finished] == [0]
        assert [q.uid for q in plan.prefills] == [1]
        assert s.pool.block_table(0) == []

    def test_preemption_evicts_youngest_and_requeues(self):
        # 6 usable pages, page_size 4: two decoding sequences that both
        # cross a page boundary cannot both fit
        s = self._sched(max_running=2, n_pages=7, page_size=4)
        a = s.submit(Request(uid=0, prompt=[1] * 8), arrival=0.0)   # 3 pages
        b = s.submit(Request(uid=1, prompt=[1] * 8), arrival=1.0)
        plan = s.step(now=2.0)
        assert {q.uid for q in plan.prefills} == {0, 1}
        for seq in (a, b):
            seq.n_prefilled = seq.prefill_target   # engine ran the prefill
            seq.generated.extend([7] * 4)     # decode to a page boundary
        plan = s.step(now=3.0)
        assert [q.uid for q in plan.preempted] == [1], "youngest loses"
        assert b.slot == -1 and s.pool.block_table(1) == []
        assert s.waiting[0].uid == 1
        assert b.full_prompt == [1] * 8 + [7] * 4  # recompute-style requeue
        assert [q.uid for q in plan.decodes] == [0]


@pytest.fixture(scope="module")
def tiny():
    cfg = ModelConfig(name="tiny", arch_type="dense", n_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                      vocab_size=259, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


MIXED_PROMPTS = [[1, 2, 3, 4, 5], [7, 8, 9], [10, 11, 12, 13, 14, 15, 16],
                 [5, 4, 3], [9, 9, 2, 1]]


class TestContinuousEngine:
    @pytest.mark.slow
    def test_greedy_token_parity_with_bucket_engine(self, tiny):
        _, model, params = tiny
        reqs = [Request(uid=i, prompt=p,
                        sampling=SamplingParams(max_new_tokens=5))
                for i, p in enumerate(MIXED_PROMPTS)]
        bc = ServingEngine(model, params, max_len=48).generate(
            reqs, max_batch=4)
        cc = ContinuousServingEngine(
            model, params, max_len=48, max_running=3,
            page_size=4).generate(reqs)
        assert [c.tokens for c in bc] == [c.tokens for c in cc]

    @pytest.mark.slow
    def test_preemption_preserves_greedy_tokens(self, tiny):
        """Starved pool: preempted sequences recompute and still match."""
        _, model, params = tiny
        reqs = [Request(uid=i, prompt=p,
                        sampling=SamplingParams(max_new_tokens=6))
                for i, p in enumerate(MIXED_PROMPTS)]
        bc = ServingEngine(model, params, max_len=48).generate(
            reqs, max_batch=4)
        eng = ContinuousServingEngine(model, params, max_len=48,
                                      max_running=3, page_size=4, n_pages=8)
        cc = eng.generate(reqs)
        assert eng.scheduler.n_preemptions > 0, "pool was not starved"
        assert [c.tokens for c in bc] == [c.tokens for c in cc]

    def test_late_arrival_admits_without_recompile(self, tiny):
        _, model, params = tiny
        reqs = [Request(uid=i, prompt=[3 + i, 5, 7],
                        sampling=SamplingParams(max_new_tokens=8))
                for i in range(4)]
        eng = ContinuousServingEngine(model, params, max_len=32,
                                      max_running=4, page_size=4)
        # request 3 arrives mid-decode of 0..2
        comps = eng.generate(reqs, arrivals=[0.0, 0.0, 0.0, 0.3])
        assert all(len(c.tokens) == 8 for c in comps)
        # one decode compilation serves every batch membership
        assert eng._decode._cache_size() == 1

    def test_prefill_pad_overrun_stays_on_scratch_page(self, tiny):
        """A prompt whose padded prefill bucket exceeds the block-table
        span (41 -> padded 64 > 6 pages * 8 slots) must not let padding
        rows clamp into the sequence's last real page."""
        _, model, params = tiny
        rng = np.random.default_rng(3)
        reqs = [Request(uid=0, prompt=list(rng.integers(1, 258, 41)),
                        sampling=SamplingParams(max_new_tokens=6))]
        bc = ServingEngine(model, params, max_len=48).generate(reqs)
        cc = ContinuousServingEngine(model, params, max_len=48,
                                     max_running=2,
                                     page_size=8).generate(reqs)
        assert bc[0].tokens == cc[0].tokens

    def test_oversized_prompt_rejected_cleanly(self, tiny):
        _, model, params = tiny
        eng = ContinuousServingEngine(model, params, max_len=32,
                                      page_size=8)
        with pytest.raises(ValueError, match="does not fit max_len"):
            eng.generate([Request(uid=0, prompt=[1] * 33)])

    def test_idle_slots_are_inert(self, tiny):
        """A lone request in a wide batch decodes as if alone."""
        _, model, params = tiny
        req = [Request(uid=0, prompt=[1, 2, 3, 4, 5],
                       sampling=SamplingParams(max_new_tokens=5))]
        wide = ContinuousServingEngine(model, params, max_len=32,
                                       max_running=8, page_size=4)
        narrow = ContinuousServingEngine(model, params, max_len=32,
                                         max_running=1, page_size=4)
        assert (wide.generate(req)[0].tokens
                == narrow.generate(req)[0].tokens)


class TestPagedKernel:
    @given(b=st.integers(1, 3), mp=st.integers(1, 4),
           g=st.sampled_from([1, 2]))
    @settings(max_examples=8, deadline=None)
    @pytest.mark.slow
    def test_pallas_kernel_matches_ref(self, b, mp, g):
        from repro.kernels.decode_attention import paged_decode_attention
        from repro.kernels.ref import paged_decode_attention_ref
        rng = np.random.default_rng(b * 100 + mp * 10 + g)
        Hkv, D, ps, P = 2, 8, 4, 9
        q = rng.normal(size=(b, Hkv, g, D)).astype(np.float32)
        kp = rng.normal(size=(P, ps, Hkv, D)).astype(np.float32)
        vp = rng.normal(size=(P, ps, Hkv, D)).astype(np.float32)
        bt = rng.integers(1, P, size=(b, mp)).astype(np.int32)
        lens = rng.integers(0, mp * ps + 1, size=(b,)).astype(np.int32)
        for window in (0, 3):
            ref = paged_decode_attention_ref(jnp.asarray(q), kp, vp, bt,
                                             lens, window)
            ker = paged_decode_attention(
                jnp.asarray(q), jnp.asarray(kp.reshape(P, ps, Hkv * D)),
                jnp.asarray(vp.reshape(P, ps, Hkv * D)), bt, lens, window,
                scale=D ** -0.5, interpret=True)
            np.testing.assert_allclose(np.asarray(ref), np.asarray(ker),
                                       rtol=1e-5, atol=1e-5)

    # windows of 3 and 6 slots start mid-page; 83 slots start five pages
    # and three slots back, mid-block for every lane it cuts
    @pytest.mark.parametrize("window", [0, 3, 6, 83])
    def test_walk_reads_only_visible_pages(self, window):
        """Ragged lanes in one batch over a pool in which every page no
        lane can see is NaN: unmapped pages, pages past a lane's last
        slot and pages before its window.  A page the kernel scored
        would poison ``p @ v`` even fully masked, so a finite output
        equal to the oracle's (run with those pages zeroed) shows the
        walk never touches them.  Lanes end one slot into a block,
        exactly on a block boundary and one slot before one, so a last
        block's rows past the lane's last page are never fetched and
        never reach ``p @ v``.  The idle lane (``kv_len`` 0) returns
        0."""
        # (page size, pages a lane): a table shorter than a block, so the
        # block is clamped to the table, and a table of several blocks
        for ps, mp in ((4, 4), (16, 40)):
            self._check_walk(window, ps, mp)

    @staticmethod
    def _check_walk(window, ps, mp):
        from repro.kernels.decode_attention import (BLOCK_TOKENS,
                                                    pages_per_block,
                                                    paged_decode_attention)
        from repro.kernels.ref import paged_decode_attention_ref
        Hkv, G, D = 2, 2, 8
        ppb = pages_per_block(ps, mp)
        assert ppb == min(mp, BLOCK_TOKENS // ps)
        assert ppb == mp if mp == 4 else mp // ppb >= 3
        blk = ppb * ps
        lens = [0, 1, ps, ps + 1, blk + 1, 2 * blk, 2 * blk - 1, mp * ps]
        lens = np.array(sorted({n for n in lens if n <= mp * ps}), np.int32)
        B, P = len(lens), 1 + len(lens) * mp
        rng = np.random.default_rng(window)
        bt = (1 + rng.permutation(B * mp)).reshape(B, mp).astype(np.int32)
        q = jnp.asarray(rng.normal(size=(B, Hkv, G, D)), jnp.float32)
        kp = rng.normal(size=(P, ps, Hkv, D)).astype(np.float32)
        vp = rng.normal(size=(P, ps, Hkv, D)).astype(np.float32)
        seen = np.zeros(P, bool)
        for b, n in enumerate(lens):
            lo = max(n - window, 0) if window else 0
            seen[bt[b, lo // ps:(n + ps - 1) // ps]] = True
        hidden = ~seen[:, None, None, None]
        ker = np.asarray(paged_decode_attention(
            q, jnp.asarray(np.where(hidden, np.nan, kp).reshape(P, ps, -1)),
            jnp.asarray(np.where(hidden, np.nan, vp).reshape(P, ps, -1)),
            bt, lens, window, scale=D ** -0.5, interpret=True))
        ref = np.asarray(paged_decode_attention_ref(
            q, jnp.asarray(np.where(hidden, 0.0, kp)),
            jnp.asarray(np.where(hidden, 0.0, vp)), bt, lens, window))
        assert np.isfinite(ker).all()
        np.testing.assert_allclose(ker, ref, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(ker[0], 0.0)


    def test_walk_plan_fetches_each_visible_page_once(self):
        """The walk's plan, lane by lane: its blocks cover exactly the
        pages from its first to its last visible one, in
        ``cdiv(pages, pages_per_block)`` blocks; each lane's first block
        takes the buffer slot after the previous live lane's last; and
        each lane hands over to the next lane with a block to fetch."""
        from repro.kernels import decode_attention as da
        ps, mp, window = 16, 40, 83
        ppb = da.pages_per_block(ps, mp)
        lens = np.array([0, 1, 129, 256, 255, 0, 640, 17], np.int32)
        plan = np.asarray(da._walk_plan(jnp.asarray(lens), window, ps, mp,
                                        ppb))
        first, last, n_blocks, start, nxt = plan
        live = [b for b, n in enumerate(lens) if n > 0]
        assert nxt[0] == live[0] and nxt[-1] == len(lens)
        walked = 0
        for b, n in enumerate(lens):
            lo = max(n - window, 0) // ps
            want = list(range(lo, -(-n // ps))) if n else []
            got = [first[b] + j * ppb + i for j in range(n_blocks[b])
                   for i in range(ppb) if first[b] + j * ppb + i <= last[b]]
            assert got == want, b
            assert n_blocks[b] == -(-len(want) // ppb)
            assert start[b] == walked
            walked += n_blocks[b]
            assert nxt[b + 1] == next((c for c in live if c > b), len(lens))


class TestScanEscapeLayout:
    """Per-layer paged-pool buffers outside the layer-scan carry.

    The compiled decode/prefill step must (a) hold each layer's K/V
    pool as an independent buffer XLA can donate, (b) update those
    buffers in place (output aliases input — no O(pool bytes) copy per
    step), and (c) produce results that do not depend on how large the
    pool is, only on the pages the block table maps.
    """

    def _paged_cache(self, model, n_pages, *, B=2, max_len=32, ps=4,
                     ctx=8, seed_rows=False):
        cache = model.init_cache(B, max_len, page_size=ps,
                                 n_pages=n_pages)
        pps = ctx // ps + 1                  # resident ctx + decode page
        bt = np.zeros((B, max_len // ps), np.int32)
        for b in range(B):
            bt[b, :pps] = 1 + b * pps + np.arange(pps)
        cache["block_tables"] = jnp.asarray(bt)
        if seed_rows:
            # deterministic resident K/V in the mapped rows only: the
            # same physical rows exist in every pool size, so results
            # must match exactly across the sweep
            rows = np.concatenate([
                bt[b, :ctx // ps].repeat(ps) * ps
                + np.tile(np.arange(ps), ctx // ps)
                for b in range(B)])
            for i, lyr in enumerate(cache["layers"]):
                HD = lyr["self"]["k"].shape[1]
                vals = (np.arange(len(rows) * HD, dtype=np.float32)
                        .reshape(len(rows), HD) % 7 - 3) * 0.1 * (i + 1)
                lyr["self"]["k"] = lyr["self"]["k"].at[rows].set(vals)
                lyr["self"]["v"] = lyr["self"]["v"].at[rows].set(-vals)
        return cache

    def test_cache_layers_are_independent_buffers(self, tiny):
        cfg, model, _ = tiny
        cache = model.init_cache(2, 32, page_size=4, n_pages=9)
        layers = cache["layers"]
        assert isinstance(layers, list) and len(layers) == cfg.n_layers
        shape = (9 * 4, cfg.n_kv_heads * (64 // 4))
        for lyr in layers:
            assert lyr["self"]["k"].shape == shape
            assert lyr["self"]["v"].shape == shape

    def test_decode_step_aliases_donated_buffers_in_place(self, tiny):
        """With donation, every layer buffer's output must reuse the
        input's device memory — the step costs O(touched bytes)."""
        _, model, params = tiny
        ps, B = 4, 2
        decode = jax.jit(
            lambda p, c, t, pos: model.decode_step(p, c, t, pos,
                                                   page_size=ps),
            donate_argnums=1)
        cache = self._paged_cache(model, n_pages=11, ps=ps, B=B)
        toks = jnp.ones((B, 1), jnp.int32)
        pos = jnp.full((B,), 8, jnp.int32)
        _, cache = decode(params, cache, toks, pos)      # compile+warm
        ptr_in = [lyr["self"][kv].unsafe_buffer_pointer()
                  for lyr in cache["layers"] for kv in ("k", "v")]
        _, cache = decode(params, cache, toks, pos)
        ptr_out = [lyr["self"][kv].unsafe_buffer_pointer()
                   for lyr in cache["layers"] for kv in ("k", "v")]
        assert ptr_in == ptr_out

    def test_decode_pool_size_invariance(self, tiny):
        """8x pool sweep at identical touched pages: logits and the
        touched cache rows must be bit-identical."""
        _, model, params = tiny
        ps, B, ctx = 4, 2, 8
        decode = jax.jit(
            lambda p, c, t, pos: model.decode_step(p, c, t, pos,
                                                   page_size=ps))
        toks = jnp.asarray([[3], [7]], jnp.int32)
        pos = jnp.full((B,), ctx, jnp.int32)
        results = {}
        for P in (11, 88):
            cache = self._paged_cache(model, P, ps=ps, B=B, ctx=ctx,
                                      seed_rows=True)
            logits, nc = decode(params, cache, toks, pos)
            touched = [np.asarray(lyr["self"][kv][:11 * ps])
                       for lyr in nc["layers"] for kv in ("k", "v")]
            results[P] = (np.asarray(logits), touched)
        np.testing.assert_array_equal(results[11][0], results[88][0])
        for a, b in zip(results[11][1], results[88][1]):
            np.testing.assert_array_equal(a, b)

    def test_prefill_chunk_pool_size_invariance(self, tiny):
        """Resumed prefill chunk over the same resident context must
        also be pool-size independent."""
        _, model, params = tiny
        ps, B, ctx = 4, 2, 8
        prefill = jax.jit(
            lambda p, b, c, slot, plen, start: model.prefill_paged(
                p, b, c, slot, plen, start=start, ctx_pages=4,
                page_size=ps))
        chunk = {"tokens": jnp.asarray([[5, 6, 7, 8]], jnp.int32)}
        out = {}
        for P in (11, 88):
            cache = self._paged_cache(model, P, ps=ps, B=B, ctx=ctx,
                                      seed_rows=True)
            logits, _ = prefill(params, chunk, cache,
                                jnp.asarray(1, jnp.int32),
                                jnp.asarray(4, jnp.int32),
                                jnp.asarray(ctx, jnp.int32))
            out[P] = np.asarray(logits)
        np.testing.assert_array_equal(out[11], out[88])
