"""Prefix caching + chunked prefill (PR: refcounted prefix sharing).

Pool level: refcount lifecycle (a referenced page is never freed),
share/release protocol, copy-on-write via ``ensure_writable``, prefix
hash-map matching (full pages, mid-page divergence, positional chain).
Scheduler level: prefix-aware admission budget, chunked prefill
interleaving with decode.  Engine level: greedy token parity of shared,
copy-on-write and chunked runs against the no-sharing baseline, with
the pool's allocation stats proving pages were actually reused.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.models import ModelConfig, build_model
from repro.serving import (ContinuousServingEngine, ContinuousScheduler,
                           KVCachePool, KVPoolConfig, Request,
                           SamplingParams)


def _pool(n_pages=17, page_size=4, prefix_cache=True):
    return KVCachePool(KVPoolConfig(
        n_pages=n_pages, page_size=page_size, n_layers=2, n_kv_heads=2,
        head_dim=8, dtype_bytes=4),
        prefix_cache=prefix_cache)


class TestRefcounts:
    def test_share_then_release_keeps_page_until_last_owner(self):
        pool = _pool(n_pages=9)
        assert pool.grow(0, 8)                    # 2 pages
        shared = pool.block_table(0)
        pool.share_pages(1, shared)
        assert all(pool.refcount(p) == 2 for p in shared)
        pool.release(0)
        # still referenced by uid 1: not freed, not reusable
        assert all(pool.refcount(p) == 1 for p in shared)
        assert pool.block_table(1) == shared
        assert pool.n_free() == 8 - 2
        pool.release(1)
        assert pool.n_free() == 8
        assert pool.n_live() == 0

    def test_cannot_share_dead_or_scratch_pages(self):
        pool = _pool(n_pages=9)
        with pytest.raises(ValueError, match="not live"):
            pool.share_pages(1, [3])
        with pytest.raises(ValueError, match="not live"):
            pool.share_pages(1, [0])

    def test_ensure_writable_clones_shared_page(self):
        pool = _pool(n_pages=9)
        pool.grow(0, 4)                           # 1 page
        [src] = pool.block_table(0)
        pool.share_pages(1, [src])
        assert pool.ensure_writable(1, 2)
        [dst] = pool.block_table(1)
        assert dst != src
        assert pool.refcount(src) == 1 and pool.refcount(dst) == 1
        assert pool.drain_copies() == [(src, dst)]
        assert pool.block_table(0) == [src], "donor table untouched"

    def test_ensure_writable_noop_on_private_page(self):
        pool = _pool(n_pages=9)
        pool.grow(0, 4)
        [pid] = pool.block_table(0)
        assert pool.ensure_writable(0, 3)
        assert pool.block_table(0) == [pid]
        assert pool.drain_copies() == []

    def test_ensure_writable_fails_when_pool_dry(self):
        pool = _pool(n_pages=3)                   # 2 usable pages
        pool.grow(0, 4)
        pool.grow(1, 4)
        pool.share_pages(2, pool.block_table(0))
        assert not pool.ensure_writable(2, 0), "no page for the clone"

    @given(ops=st.lists(st.integers(0, 11), min_size=1, max_size=60))
    @settings(max_examples=25, deadline=None)
    def test_referenced_pages_never_reach_free_list(self, ops):
        """Random grow/share/release interleavings: every block-table
        entry stays live (refcount >= 1, not in a free list), refcounts
        equal the number of tables holding the page, and free + live
        always account for the whole usable pool."""
        pool = _pool(n_pages=13)
        for op in ops:
            uid = op % 3
            if op < 6:
                want = 4 * (len(pool.block_table(uid)) + 1)
                if pool.cfg.pages_for(want) <= pool.cfg.max_pages_per_seq:
                    pool.grow(uid, want)
            elif op < 9:
                donor = (uid + 1) % 3
                if pool.block_table(donor):
                    pool.share_pages(uid, pool.block_table(donor)[:1])
            else:
                pool.release(uid)
            free = set(pool._free)
            held = {}
            for u in range(3):
                for p in pool.block_table(u):
                    assert p != 0, "scratch page leaked"
                    assert p not in free, f"page {p} live AND free"
                    held[p] = held.get(p, 0) + 1
            for p, n in held.items():
                assert pool.refcount(p) == n
            assert pool.n_live() + pool.n_free() == pool.cfg.n_pages - 1


class TestPrefixMatching:
    def test_full_page_prefix_match(self):
        pool = _pool()
        pool.grow(0, 9)                           # prompt 8 + decode slot
        pool.register_prefix(0, list(range(1, 9)))
        m = pool.match_prefix(list(range(1, 9)) + [99])   # 9 tokens
        assert list(m.pages) == pool.block_table(0)[:2]
        assert m.n_tokens == 8 and m.cow_src is None

    def test_match_caps_one_token_below_identical_prompt(self):
        """An exact duplicate must still prefill >= 1 token (for the
        first sample's logits); the final page is cloned, not shared."""
        pool = _pool()
        pool.grow(0, 9)
        prompt = list(range(1, 9))
        pool.register_prefix(0, prompt)
        m = pool.match_prefix(prompt)             # limit = 7
        assert list(m.pages) == pool.block_table(0)[:1]
        assert m.cow_src == pool.block_table(0)[1] and m.cow_len == 3
        assert m.n_tokens == 7

    def test_mid_page_divergence_is_cow(self):
        pool = _pool()
        pool.grow(0, 9)
        pool.register_prefix(0, [1, 2, 3, 4, 5, 6, 7, 8])
        m = pool.match_prefix([1, 2, 3, 4, 5, 6, 200, 201, 202])
        assert m.n_tokens == 6                    # page 0 + 2 tokens
        assert m.cow_src == pool.block_table(0)[1] and m.cow_len == 2

    def test_chain_hash_is_position_sensitive(self):
        """The same block content at a different block index must not
        match — KV depends on absolute position (RoPE)."""
        pool = _pool()
        pool.grow(0, 9)
        pool.register_prefix(0, [1, 2, 3, 4, 5, 6, 7, 8])
        m = pool.match_prefix([5, 6, 7, 8, 50, 51])
        assert m.n_tokens == 0 and not m.pages and m.cow_src is None

    def test_entries_die_with_their_page(self):
        """Without retention, release forgets the entries immediately;
        with it (default), they survive until the LRU evicts the page."""
        pool = KVCachePool(KVPoolConfig(
            n_pages=17, page_size=4, n_layers=2, n_kv_heads=2,
            head_dim=8, dtype_bytes=4), retain=False)
        pool.grow(0, 9)
        prompt = list(range(1, 9))
        pool.register_prefix(0, prompt)
        pool.release(0)
        m = pool.match_prefix(prompt + [99])
        assert m.n_tokens == 0 and not m.pages

    def test_adopt_prefix_shares_and_clones(self):
        pool = _pool()
        pool.grow(0, 9)
        pool.register_prefix(0, [1, 2, 3, 4, 5, 6, 7, 8])
        m = pool.match_prefix([1, 2, 3, 4, 5, 6, 9, 9, 9])
        assert pool.adopt_prefix(1, m)
        table = pool.block_table(1)
        assert table[0] == pool.block_table(0)[0]         # shared
        assert pool.refcount(table[0]) == 2
        assert table[1] != pool.block_table(0)[1]         # CoW clone
        assert pool.drain_copies() == [(pool.block_table(0)[1], table[1])]
        # the clone + share satisfy 6 of the 9 tokens; grow covers rest
        assert pool.grow(1, 10)
        assert len(table) != 0 and pool.stats["cow_copies"] == 1


class TestRetention:
    """Prefix-page retention LRU: refcount-0 pages that are prefix-
    indexed retire to a cached-free list instead of being forgotten,
    and are evicted (LRU) only when the free lists run dry."""

    def test_release_retains_indexed_pages(self):
        pool = _pool(n_pages=17)
        pool.grow(0, 9)                           # 3 pages (2 indexed)
        prompt = list(range(1, 9))
        pool.register_prefix(0, prompt)
        pool.release(0)
        assert pool.n_live() == 0
        assert pool.n_retained() == 2             # indexed full pages
        assert pool.n_free() == 16                # retained still count
        m = pool.match_prefix(prompt + [99])
        assert m.n_tokens == 8 and len(m.pages) == 2

    def test_adopt_revives_retained_pages(self):
        pool = _pool(n_pages=17)
        pool.grow(0, 9)
        prompt = list(range(1, 9))
        pool.register_prefix(0, prompt)
        pool.release(0)
        m = pool.match_prefix(prompt + [99])
        assert pool.adopt_prefix(1, m)
        assert pool.n_retained() == 0
        assert all(pool.refcount(p) == 1 for p in m.pages)
        assert pool.stats["retention_hits"] == 2
        pool.release(1)                           # back to retained
        assert pool.n_retained() == 2

    def test_eviction_when_free_list_runs_dry(self):
        pool = _pool(n_pages=5)                   # 4 usable pages
        pool.grow(0, 9)                           # takes 3
        prompt = list(range(1, 9))
        pool.register_prefix(0, prompt)
        pool.release(0)                           # 2 retained + 2 free
        assert pool.grow(1, 16)                   # needs all 4 pages
        assert pool.n_retained() == 0
        assert pool.stats["retained_evictions"] == 2
        m = pool.match_prefix(prompt + [99])      # entries died at evict
        assert m.n_tokens == 0 and not m.pages

    def test_lru_evicts_oldest_retirement_first(self):
        pool = _pool(n_pages=9)                   # 8 usable
        pool.grow(0, 5)                           # 2 pages, 1 indexed
        pool.register_prefix(0, [1, 2, 3, 4])
        pool.grow(1, 5)
        pool.register_prefix(1, [5, 6, 7, 8])
        pool.release(0)                           # retired first
        pool.release(1)
        assert pool.n_retained() == 2
        assert pool.grow(2, 4 * (8 - 2 + 1))      # force ONE eviction
        assert pool.match_prefix([1, 2, 3, 4, 9]).n_tokens == 0
        assert pool.match_prefix([5, 6, 7, 8, 9]).n_tokens == 4

    def test_admission_budget_counts_matched_retained_pages_once(self):
        """A matched retained page is both 'shared, not allocated' AND
        part of n_free()'s reclaimable count — the budget must not use
        it twice.  Here the prompt's tail needs 2 pages and n_free()
        says 2, but one of those IS the matched retained page:
        admission must refuse cleanly instead of adopt-then-rollback
        (which would inflate stats on every retried step)."""
        prompt = list(range(1, 9))                # 2 full pages @ ps=4
        pool = _pool(n_pages=5)                   # 4 usable pages
        pool.grow(0, 9)                           # donor: 3 pages
        pool.register_prefix(0, prompt)
        pool.release(0)                           # 2 retained, 2 free
        pool.grow(9, 8)                           # bystander eats the
        assert pool.n_retained() == 2             # 2 true-free pages
        assert pool.n_free() == 2                 # both are retained
        sched = ContinuousScheduler(pool, max_running=4, max_len=64)
        # repeat prompt: 1 retained page + CoW match; tail needs the
        # clone + decode page = 2, but reviving the match leaves 1
        sched.submit(Request(uid=1, prompt=list(prompt)))
        before = dict(pool.stats)
        plan = sched.step()
        assert not plan.prefills and not sched.running
        assert pool.stats["retention_hits"] == before["retention_hits"]
        assert pool.stats["shared_pages"] == before["shared_pages"]
        assert pool.n_retained() == 2             # LRU undisturbed

    def test_cow_only_match_against_retained_page(self):
        """Divergence inside the FIRST block of a retained prompt:
        the match shares no full page, only a CoW clone — adoption
        must create the block table from scratch (regression: KeyError
        when the clone was the table's first entry)."""
        pool = _pool(n_pages=9, page_size=8)
        pool.grow(0, 9)
        pool.register_prefix(0, list(range(1, 9)))
        pool.release(0)                           # first page retained
        m = pool.match_prefix([1, 2, 3, 200, 201])
        assert not m.pages and m.cow_src is not None and m.cow_len == 3
        assert pool.adopt_prefix(1, m)
        assert len(pool.block_table(1)) == 1
        assert pool.pending_copies == [(m.cow_src, pool.block_table(1)[0])]

    @pytest.mark.slow
    def test_repeat_prompt_hits_cache_after_first_request_finished(
            self, tiny):
        """The cross-request claim: serve a prompt, let the request
        finish completely (refcounts at 0), serve it again — the repeat
        must hit retained pages, not re-prefill, with identical greedy
        tokens."""
        _, model, params = tiny
        req = Request(uid=0, prompt=SHARED_PREFIX + [31, 32, 33],
                      sampling=SamplingParams(max_new_tokens=6))
        eng = ContinuousServingEngine(model, params, max_len=64,
                                      max_running=4, page_size=4)
        first = eng.generate([req])
        assert eng.pool.n_live() == 0             # fully finished
        assert eng.pool.n_retained() > 0
        again = eng.generate([req])
        assert [c.tokens for c in again] == [c.tokens for c in first]
        assert eng.pool.stats["retention_hits"] > 0
        assert eng.pool.stats["cached_tokens"] >= len(SHARED_PREFIX)


class TestSchedulerPrefix:
    def test_cached_pages_do_not_count_against_budget(self):
        """A mostly-cached prompt admits into a pool too full for a cold
        one: 5 usable pages, donor holds 3, prompt needs 3 — only 2 are
        free, but sharing covers the difference."""
        prompt = list(range(1, 9))                # 8 tokens, ps=4
        for cached, want_admitted in ((True, True), (False, False)):
            pool = _pool(n_pages=6, prefix_cache=cached)
            sched = ContinuousScheduler(pool, max_running=4, max_len=64)
            donor = sched.submit(Request(uid=0, prompt=prompt))
            plan = sched.step()
            assert [s.uid for s in plan.prefills] == [0]
            donor.n_prefilled = donor.prefill_target   # engine ran it
            pool.register_prefix(0, prompt)
            assert pool.n_free() == 2
            sched.submit(Request(uid=1, prompt=list(prompt)))
            plan = sched.step()
            admitted = any(s.uid == 1 for s in plan.prefills)
            assert admitted == want_admitted
            if cached:
                # shared full page + CoW clone: only 1 token to prefill
                seq = next(s for s in plan.prefills if s.uid == 1)
                assert seq.n_prefilled == 7 and seq.prefill_target == 8
                assert pool.stats["shared_pages"] == 1
                assert pool.stats["cow_copies"] == 1

    def test_chunked_prefill_never_blocks_decode(self):
        """A long-prompt admission runs as fixed-size chunks, one per
        step, while the resident sequence decodes in *every* step."""
        pool = _pool(n_pages=17)
        sched = ContinuousScheduler(pool, max_running=4, max_len=64,
                                    prefill_chunk=2)
        a = sched.submit(Request(uid=0, prompt=[1, 2, 3]))
        plan = sched.step()
        a.n_prefilled = a.prefill_target
        a.generated.append(7)                     # engine sampled
        b = sched.submit(Request(uid=1, prompt=list(range(10, 20))))
        steps = 0
        while b.is_prefilling or b.slot == -1:
            plan = sched.step()
            assert [s.uid for s in plan.decodes] == [0], \
                "decode must run every step during the long admission"
            assert [s.uid for s in plan.prefills] == [1]
            n = sched.chunk_for(b)
            assert 0 < n <= 2
            b.n_prefilled += n                    # engine ran the chunk
            a.generated.append(7)                 # engine decoded a
            steps += 1
            assert steps < 20
        assert steps == 5                         # ceil(10 / 2)
        plan = sched.step()                       # b decodes from now on
        b.generated.append(9)
        assert {s.uid for s in plan.decodes} == {0, 1}

    def test_preempted_mid_prefill_restarts_clean(self):
        pool = _pool(n_pages=5)                   # 4 usable pages
        sched = ContinuousScheduler(pool, max_running=2, max_len=64,
                                    prefill_chunk=2)
        a = sched.submit(Request(uid=0, prompt=[1] * 6), arrival=0.0)
        sched.step()
        a.n_prefilled = a.prefill_target
        a.generated.append(7)
        b = sched.submit(Request(uid=1, prompt=[2] * 8), arrival=1.0)
        plan = sched.step(now=1.0)                # b admitted? needs 3 pages
        assert plan.prefills == []                # only 2 free: stays queued
        # decode a across its page boundary until the pool forces action
        a.generated.extend([7] * 6)
        plan = sched.step(now=2.0)
        assert a.slot != -1 and pool.block_table(1) == []
        assert b in sched.waiting


@pytest.fixture(scope="module")
def tiny():
    cfg = ModelConfig(name="tiny", arch_type="dense", n_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                      vocab_size=259, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


SHARED_PREFIX = [11, 12, 13, 14, 21, 22, 23, 24]          # 2 full ps=4 pages


def _greedy(prompt_suffixes, max_new=6):
    return [Request(uid=i, prompt=SHARED_PREFIX + s,
                    sampling=SamplingParams(max_new_tokens=max_new))
            for i, s in enumerate(prompt_suffixes)]


class TestEnginePrefixChunking:
    def _run(self, model, params, reqs, *, arrivals=None, **kw):
        eng = ContinuousServingEngine(model, params, max_len=64,
                                      max_running=4, page_size=4, **kw)
        comps = eng.generate(reqs, arrivals=arrivals)
        return eng, [c.tokens for c in comps]

    @pytest.mark.slow
    def test_shared_prefix_parity_and_page_savings(self, tiny):
        _, model, params = tiny
        suffixes = [[31, 32, 33], [41, 42, 43], [51, 52]]
        # staggered arrivals so later requests admit after the donor's
        # prompt pages are resident and registered
        arrivals = [0.0, 0.05, 0.1]
        e_off, toks_off = self._run(model, params, _greedy(suffixes),
                                    arrivals=arrivals, prefix_cache=False)
        e_on, toks_on = self._run(model, params, _greedy(suffixes),
                                  arrivals=arrivals, prefix_cache=True)
        assert toks_on == toks_off, "sharing must not change greedy tokens"
        assert e_on.pool.stats["shared_pages"] >= 2, "prefix pages reused"
        assert (e_on.pool.stats["fresh_pages"]
                < e_off.pool.stats["fresh_pages"])
        assert e_on.pool.stats["cached_tokens"] >= 8

    @pytest.mark.slow
    def test_cow_divergence_parity(self, tiny):
        """Second request diverges mid-page: first page shares, second
        page clones (copy-on-write) and only the suffix recomputes."""
        _, model, params = tiny
        a = Request(uid=0, prompt=[1, 2, 3, 4, 5, 6, 7, 8],
                    sampling=SamplingParams(max_new_tokens=6))
        b = Request(uid=1, prompt=[1, 2, 3, 4, 5, 6, 200, 201, 202],
                    sampling=SamplingParams(max_new_tokens=6))
        arrivals = [0.0, 0.05]
        e_off, toks_off = self._run(model, params, [a, b],
                                    arrivals=arrivals, prefix_cache=False)
        e_on, toks_on = self._run(model, params, [a, b],
                                  arrivals=arrivals, prefix_cache=True)
        assert toks_on == toks_off
        assert e_on.pool.stats["cow_copies"] >= 1
        assert e_on.pool.stats["shared_pages"] >= 1

    @pytest.mark.slow
    def test_chunked_prefill_parity(self, tiny):
        """Chunked prefill (including a 17-token prompt spread over many
        steps) produces the same greedy tokens as one-shot prefill."""
        _, model, params = tiny
        rng = np.random.default_rng(11)
        reqs = [Request(uid=i, prompt=list(rng.integers(1, 258, n)),
                        sampling=SamplingParams(max_new_tokens=5))
                for i, n in enumerate([17, 3, 9, 6])]
        _, toks_one = self._run(model, params, reqs, prefix_cache=False)
        _, toks_chunk = self._run(model, params, reqs, prefix_cache=False,
                                  prefill_chunk=4)
        assert toks_chunk == toks_one

    @pytest.mark.slow
    def test_chunked_plus_prefix_parity(self, tiny):
        _, model, params = tiny
        suffixes = [[31, 32, 33, 34, 35], [41, 42, 43, 44]]
        # long decode keeps the donor resident across the second arrival
        reqs = _greedy(suffixes, max_new=48)
        arrivals = [0.0, 0.02]
        _, base = self._run(model, params, reqs,
                            arrivals=arrivals, prefix_cache=False)
        eng = ContinuousServingEngine(model, params, max_len=64,
                                      max_running=4, page_size=4,
                                      prefix_cache=True, prefill_chunk=4)
        # warm every chunk-shape compile so the measured run's steps are
        # milliseconds — the donor then finishes (and registers) its
        # chunked prefill well before the second arrival at 0.02 s
        eng.generate(reqs)
        assert eng.pool.n_live() == 0             # warm run fully drained
        eng.pool.stats["shared_pages"] = 0
        toks = [c.tokens for c in eng.generate(reqs, arrivals=arrivals)]
        assert toks == base
        assert eng.pool.stats["shared_pages"] >= 1

    def test_pool_drains_clean_after_generate(self, tiny):
        _, model, params = tiny
        e, _ = self._run(model, params, _greedy([[31], [41, 42]]),
                         arrivals=[0.0, 0.05])
        assert e.pool.n_live() == 0
        assert e.pool.n_free() == e.pool.cfg.n_pages - 1
        assert e.pool.pending_copies == []


class TestPerLayerCopies:
    """CoW page copies against the per-layer (scan-escape) cache
    layout: one (src_rows, dst_rows) plan serves every layer buffer."""

    def test_copy_row_plan_expands_pages_to_rows(self):
        pool = _pool(n_pages=9, page_size=4)
        src, dst = pool.copy_row_plan([(2, 5)])
        assert src.tolist() == [8, 9, 10, 11]
        assert dst.tolist() == [20, 21, 22, 23]

    def test_copy_row_plan_pads_with_scratch_noops(self):
        pool = _pool(n_pages=9, page_size=4)
        src, dst = pool.copy_row_plan([(2, 5)], pad_to_pages=4)
        assert src.shape == dst.shape == (16,)
        # pad rows are 0 -> 0: a self-copy into the reserved scratch
        # page, invisible to every live sequence
        assert src[4:].tolist() == [0] * 12
        assert dst[4:].tolist() == [0] * 12
        with pytest.raises(ValueError):
            pool.copy_row_plan([(2, 5), (3, 6)], pad_to_pages=1)

    def test_apply_copies_touches_every_layer_buffer(self, tiny):
        """A queued CoW copy must land in ALL per-layer K and V buffers
        in one dispatch, and leave the runner cache rebound to the
        copied (donated) buffers."""
        _, model, params = tiny
        eng = ContinuousServingEngine(model, params, max_len=32,
                                      max_running=2, page_size=4)
        runner = eng.core.runner
        ps = 4
        src_page, dst_page = 2, 5
        rows = np.arange(src_page * ps, (src_page + 1) * ps)
        for i, lyr in enumerate(runner.cache["layers"]):
            vals = np.full((ps, lyr["self"]["k"].shape[1]), float(i + 1),
                           np.float32)
            lyr["self"]["k"] = lyr["self"]["k"].at[rows].set(vals)
            lyr["self"]["v"] = lyr["self"]["v"].at[rows].set(-vals)
        eng.pool.pending_copies.append((src_page, dst_page))
        eng.core._apply_copies()
        assert eng.pool.pending_copies == []
        drows = np.arange(dst_page * ps, (dst_page + 1) * ps)
        for i, lyr in enumerate(runner.cache["layers"]):
            np.testing.assert_array_equal(
                np.asarray(lyr["self"]["k"][drows]),
                np.full_like(np.asarray(lyr["self"]["k"][drows]),
                             float(i + 1)))
            np.testing.assert_array_equal(
                np.asarray(lyr["self"]["v"][drows]),
                np.full_like(np.asarray(lyr["self"]["v"][drows]),
                             -float(i + 1)))


class TestBenchGate:
    """tools/bench_gate.py regression logic (pure compare path)."""

    def _report(self, **vals):
        metrics = {}
        for name, (value, direction) in vals.items():
            metrics[name] = {"value": value, "direction": direction}
        return {"metrics": metrics}

    def test_injected_regression_fails_gate(self, tmp_path):
        import json
        import subprocess
        import sys
        import os
        root = os.path.join(os.path.dirname(__file__), "..")
        base = self._report(decode_tok_per_s=(100.0, "higher"),
                            decode_flatness=(1.0, "lower"))
        # decode throughput fell 40% — far past the 20% threshold
        cur = self._report(decode_tok_per_s=(60.0, "higher"),
                           decode_flatness=(1.0, "lower"))
        bp, cp = tmp_path / "base.json", tmp_path / "cur.json"
        bp.write_text(json.dumps(base))
        cp.write_text(json.dumps(cur))
        r = subprocess.run(
            [sys.executable, os.path.join(root, "tools", "bench_gate.py"),
             "compare", str(cp), str(bp)],
            capture_output=True, text=True)
        assert r.returncode == 1, r.stdout + r.stderr
        assert "REGRESSION" in r.stderr

    def test_within_threshold_passes(self):
        import sys
        import os
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "tools"))
        import bench_gate
        base = self._report(decode_tok_per_s=(100.0, "higher"),
                            max_decode_gap_ms=(10.0, "lower"))
        cur = self._report(decode_tok_per_s=(85.0, "higher"),
                           max_decode_gap_ms=(11.5, "lower"))
        assert bench_gate.compare(cur, base, threshold=0.20) == []
        # lower-is-better direction regresses upward
        worse = self._report(decode_tok_per_s=(100.0, "higher"),
                             max_decode_gap_ms=(13.0, "lower"))
        regs = bench_gate.compare(worse, base, threshold=0.20)
        assert len(regs) == 1 and "max_decode_gap_ms" in regs[0]

    def test_missing_metrics_are_skipped(self):
        import sys
        import os
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "tools"))
        import bench_gate
        base = self._report(old_metric=(5.0, "lower"))
        cur = self._report(new_metric=(1.0, "lower"))
        assert bench_gate.compare(cur, base, threshold=0.2) == []

    def test_new_and_dropped_metrics_are_reported_not_failed(self):
        """A metric present only in the current run (first run of a
        fresh bench, e.g. serving_tp.*) must neither fail the gate nor
        vanish silently — ``schema_drift`` names it as ``new``; one
        only in the baseline is named ``dropped``."""
        import sys
        import os
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "tools"))
        import bench_gate
        base = self._report(decode_tok_per_s=(100.0, "higher"),
                            old_metric=(5.0, "lower"))
        cur = self._report(decode_tok_per_s=(99.0, "higher"),
                           tp_decode_tok_per_s=(450.0, "higher"))
        drift = bench_gate.schema_drift(cur, base)
        assert len(drift) == 2
        assert any(d.startswith("tp_decode_tok_per_s: new metric")
                   and "450" in d for d in drift)
        assert any(d.startswith("old_metric: dropped metric")
                   and "5" in d for d in drift)
        assert bench_gate.schema_drift(cur, cur) == []

    def test_compare_cli_prints_new_metric_and_passes(self, tmp_path):
        import json
        import subprocess
        import sys
        import os
        root = os.path.join(os.path.dirname(__file__), "..")
        base = self._report(decode_tok_per_s=(100.0, "higher"))
        cur = self._report(decode_tok_per_s=(100.0, "higher"),
                           tp_decode_tok_per_s=(450.0, "higher"))
        bp, cp = tmp_path / "base.json", tmp_path / "cur.json"
        bp.write_text(json.dumps(base))
        cp.write_text(json.dumps(cur))
        r = subprocess.run(
            [sys.executable, os.path.join(root, "tools", "bench_gate.py"),
             "compare", str(cp), str(bp)],
            capture_output=True, text=True)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "tp_decode_tok_per_s: new metric" in r.stdout
        assert "OK" in r.stdout

    def test_run_baseline_is_the_outfile_itself(self, tmp_path):
        """The committed BENCH_PR3.json must be read as the baseline
        BEFORE a run overwrites it — otherwise the wired gate can
        never fire (it would exclude its own output and find nothing
        to compare against)."""
        import json
        import sys
        import os
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "tools"))
        import bench_gate
        out = tmp_path / "BENCH_PR3.json"
        committed = self._report(decode_tok_per_s=(100.0, "higher"))
        out.write_text(json.dumps(committed))
        base, name = bench_gate.load_baseline(str(tmp_path), str(out))
        assert base == committed and "previous" in name
        # without the out-file, fall back to the newest other BENCH_*
        out.unlink()
        other = tmp_path / "BENCH_OLD.json"
        other.write_text(json.dumps(committed))
        base, name = bench_gate.load_baseline(str(tmp_path), str(out))
        assert base == committed and name == "BENCH_OLD.json"
        other.unlink()
        assert bench_gate.load_baseline(str(tmp_path), str(out))[0] is None
