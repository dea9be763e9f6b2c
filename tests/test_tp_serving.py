"""Tensor-parallel paged serving (PR: TP mesh over the paged engine).

Host level (fast lane): head-sharded KV pool validation and per-shard
byte accounting, and the sliding-window ``release_below`` recycling
groundwork (property-tested).

Device level (subprocess, ``slow``): everything needing a real
multi-device mesh runs in a child interpreter with forced host devices
(the in-process suite must keep the single real CPU device, see
``tests/conftest.py``):

* sharded-vs-single-shard greedy token parity, including shared-prefix,
  copy-on-write and chunked-prefill runs (the TP head merge is a
  zero-padded psum over disjoint head supports, so tokens must be
  byte-identical, not merely close);
* buffer donation still aliases each shard's per-layer pool buffers;
* ``core.tp.collective_ops_in`` on the compiled decode/prefill bodies:
  exactly one psum per layer, and no gather/scatter collective ever
  touches KV-page bytes.
"""

import os
import subprocess
import sys
import textwrap

import pytest
from _hypothesis_compat import given, settings, st

from repro.serving import KVCachePool, KVPoolConfig


def _run(snippet: str, devices: int = 2) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(snippet)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ----------------------------------------------------------------------
# head-sharded pool (fast lane)
# ----------------------------------------------------------------------

class TestShardedPool:
    @pytest.mark.parametrize("n_kv_heads,n_shards", [(2, 4), (4, 3)])
    def test_pool_config_validates_kv_head_divisibility(
            self, n_kv_heads, n_shards):
        with pytest.raises(ValueError, match="head-shard"):
            KVCachePool(KVPoolConfig(
                n_pages=9, page_size=4, n_layers=2, n_kv_heads=n_kv_heads,
                head_dim=8, n_shards=n_shards))

    def test_pool_shard_accounting(self):
        pool = KVCachePool(KVPoolConfig(
            n_pages=9, page_size=4, n_layers=2, n_kv_heads=4,
            head_dim=8, dtype_bytes=4, n_shards=2))
        assert pool.cfg.page_shard_bytes * 2 == pool.cfg.page_bytes
        per_shard = pool.capacity_bytes_per_shard()
        assert per_shard == {0: 9 * pool.cfg.page_shard_bytes,
                             1: 9 * pool.cfg.page_shard_bytes}
        assert sum(per_shard.values()) == 9 * pool.cfg.page_bytes
        # page ids are global: the free list hands out the one-shard order
        assert pool.grow(0, 16) and pool.grow(1, 16)
        assert pool.block_table(0) == [1, 2, 3, 4]
        assert pool.block_table(1) == [5, 6, 7, 8]


# ----------------------------------------------------------------------
# sliding-window page recycling groundwork
# ----------------------------------------------------------------------

def _pool(n_pages=17, page_size=4, prefix_cache=True, retain=True):
    return KVCachePool(KVPoolConfig(
        n_pages=n_pages, page_size=page_size, n_layers=2, n_kv_heads=2,
        head_dim=8, dtype_bytes=4),
        prefix_cache=prefix_cache, retain=retain)


class TestReleaseBelow:
    @given(n_tokens=st.integers(4, 60), pos=st.integers(0, 64))
    @settings(max_examples=40)
    def test_recycles_exactly_the_fully_below_pages(self, n_tokens, pos):
        pool = _pool(prefix_cache=False)
        assert pool.grow(0, n_tokens)
        table = pool.block_table(0)
        free0 = pool.n_free()
        dropped = pool.release_below(0, pos)
        expect = min(pos // 4, len(table))
        assert dropped == expect
        after = pool.block_table(0)
        assert len(after) == len(table)           # logical length kept
        assert after[:expect] == [0] * expect     # recycled -> scratch
        assert after[expect:] == table[expect:]   # tail untouched
        assert pool.n_free() == free0 + expect
        # recycled pages really are reusable
        for pid in table[:expect]:
            assert pool.refcount(pid) == 0
        # idempotent: nothing left below pos
        assert pool.release_below(0, pos) == 0
        pool.release(0)
        assert pool.n_live() == 0
        assert pool.n_free() == pool.cfg.n_pages - 1

    def test_partial_page_is_kept(self):
        pool = _pool(prefix_cache=False)
        pool.grow(0, 12)                          # 3 pages @ ps=4
        table = pool.block_table(0)
        # pos 7: page 0 fully below, page 1 still holds slot 7
        assert pool.release_below(0, 7) == 1
        assert pool.block_table(0) == [0] + table[1:]

    def test_shared_page_only_loses_one_reference(self):
        pool = _pool(prefix_cache=False)
        pool.grow(0, 8)
        shared = pool.block_table(0)
        pool.share_pages(1, shared)
        free0 = pool.n_free()
        assert pool.release_below(0, 8) == 2
        # uid 1 still owns the pages: nothing freed
        assert pool.n_free() == free0
        assert all(pool.refcount(p) == 1 for p in shared)
        pool.release(1)
        # uid0's table holds only recycled zeros now: pool fully free
        assert pool.n_free() == pool.cfg.n_pages - 1
        pool.release(0)
        assert pool.n_live() == 0

    def test_prefix_indexed_pages_retire_to_retention_lru(self):
        pool = _pool()
        tokens = list(range(1, 13))               # 3 full pages
        pool.grow(0, len(tokens) + 1)
        pool.register_prefix(0, tokens)
        table = pool.block_table(0)
        retained0 = pool.n_retained()
        assert pool.release_below(0, 8) == 2
        # both fully-below pages were prefix-indexed: cached-free LRU,
        # not the free list — a repeat prompt can still hit them
        assert pool.n_retained() == retained0 + 2
        match = pool.match_prefix(tokens + [99])
        assert match.pages == tuple(table[:3])
        pool.release(0)

    def test_growth_after_recycling_extends_the_tail(self):
        pool = _pool(n_pages=8, page_size=4, prefix_cache=False)
        pool.grow(0, 16)                          # 4 of 7 usable pages
        assert pool.release_below(0, 8) == 2
        assert pool.can_grow(0, 24)
        assert pool.grow(0, 24)                   # reuses recycled pages
        table = pool.block_table(0)
        assert len(table) == 6
        assert table[0] == table[1] == 0
        assert all(p != 0 for p in table[2:])


# ----------------------------------------------------------------------
# device level (subprocess, forced host devices)
# ----------------------------------------------------------------------

_CHILD_SETUP = """
    import numpy as np, jax
    import jax.numpy as jnp
    from repro.models import ModelConfig, build_model
    from repro.serving import (ContinuousServingEngine, Request,
                               SamplingParams)
    from repro.launch.mesh import make_mesh

    cfg = ModelConfig(name="tp-tiny", arch_type="dense", n_layers=3,
                      d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                      vocab_size=259, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
"""


@pytest.mark.slow
def test_tp_greedy_parity_incl_prefix_cow_chunked():
    """Byte-identical tokens at shards {1, 2} vs the plain engine on a
    two-wave workload that exercises prefix sharing, mid-page CoW and
    chunked prefill (stats assert the TP run really shared/cloned)."""
    print(_run(_CHILD_SETUP + """
    rng = np.random.default_rng(11)
    system = list(rng.integers(1, 258, 20))   # 2 full pages + 4 tokens
    # 26-token prompts: block 3 (tokens 16..23) fills completely and
    # registers, so wave 2's divergence at token 20 is a mid-page CoW
    wave1 = [Request(uid=i, prompt=system + list(rng.integers(1, 258, 6)),
                     sampling=SamplingParams(max_new_tokens=8))
             for i in range(2)]
    wave2 = [Request(uid=9 + i,
                     prompt=system + list(rng.integers(1, 258, 6)),
                     sampling=SamplingParams(max_new_tokens=8))
             for i in range(2)]

    def run(mesh=None):
        eng = ContinuousServingEngine(
            model, params, max_len=64, max_running=4, page_size=8,
            prefill_chunk=8, mesh=mesh)
        toks = [c.tokens for c in eng.generate(wave1)]
        toks += [c.tokens for c in eng.generate(wave2)]
        return eng, toks

    _, ref = run()
    for shards in (1, 2):
        mesh = make_mesh((shards,), ("model",))
        eng, got = run(mesh)
        assert got == ref, (shards, got, ref)
        st = eng.pool.stats
        assert st["shared_pages"] > 0, st      # prefix pages shared
        assert st["cow_copies"] > 0, st        # mid-page divergence
        assert st["retention_hits"] > 0, st    # cross-wave reuse
    print("TP-PARITY-OK")
    """))


@pytest.mark.slow
def test_tp_donation_aliases_per_shard_buffers():
    print(_run(_CHILD_SETUP + """
    mesh = make_mesh((2,), ("model",))
    eng = ContinuousServingEngine(model, params, max_len=64,
                                  max_running=4, page_size=8, mesh=mesh)
    eng.generate([Request(uid=0, prompt=[1, 2, 3],
                          sampling=SamplingParams(max_new_tokens=2))])
    r = eng.core.runner
    assert r.tp_shards == 2
    k = r.cache["layers"][0]["self"]["k"]
    # each shard holds one kv head's columns of every row
    assert [s.data.shape[1] for s in k.addressable_shards] == (
        [k.shape[1] // 2] * 2)
    ptrs0 = sorted(s.data.unsafe_buffer_pointer()
                   for s in k.addressable_shards)
    logits = r.decode(np.zeros((4, 1), np.int32),
                      np.full((4,), -1, np.int32))
    jax.block_until_ready(logits)
    k1 = r.cache["layers"][0]["self"]["k"]
    ptrs1 = sorted(s.data.unsafe_buffer_pointer()
                   for s in k1.addressable_shards)
    assert ptrs0 == ptrs1, (ptrs0, ptrs1)   # donated: scatter in place
    print("TP-DONATION-OK")
    """))


@pytest.mark.slow
def test_tp_collectives_one_psum_per_layer_no_kv_gather():
    """The §3.4 Sync-B budget: decode and prefill bodies contain exactly
    n_layers psums (the per-layer head merge) and not a single
    gather/scatter collective — KV-page bytes never cross shards."""
    print(_run(_CHILD_SETUP + """
    from repro.core.tp import collective_ops_in
    mesh = make_mesh((2,), ("model",))
    eng = ContinuousServingEngine(model, params, max_len=64,
                                  max_running=4, page_size=8, mesh=mesh)
    r = eng.core.runner
    toks = jnp.ones((4, 1), jnp.int32)
    pos = jnp.zeros((4,), jnp.int32)
    counts = collective_ops_in(r.tp_raw_decode, r.params, r.cache,
                               toks, pos)
    assert counts.get("psum") == cfg.n_layers, counts
    assert set(counts) == {"psum"}, counts

    # prefill (fresh + resumed-chunk buckets): same budget — the jitted
    # wrapper's jaxpr nests the shard_map body, which the walker visits
    batch = {"tokens": jnp.ones((1, 8), jnp.int32)}
    sl = jnp.asarray(0, jnp.int32)
    pl = jnp.asarray(8, jnp.int32)
    c_fresh = collective_ops_in(r._prefill_fn(8, 0), r.params, batch,
                                r.cache, sl, pl)
    c_chunk = collective_ops_in(r._prefill_fn(8, 4), r.params, batch,
                                r.cache, sl, pl, jnp.asarray(8, jnp.int32))
    for counts in (c_fresh, c_chunk):
        assert counts.get("psum") == cfg.n_layers, counts
        assert set(counts) == {"psum"}, counts
    print("TP-COLLECTIVES-OK")
    """))


@pytest.mark.slow
def test_tp_rejects_indivisible_heads_and_bad_policy():
    print(_run(_CHILD_SETUP + """
    from repro.launch.shardings import Policy
    mesh = make_mesh((2,), ("model",))
    bad = ModelConfig(name="odd", arch_type="dense", n_layers=2,
                      d_model=63, n_heads=3, n_kv_heads=1, d_ff=64,
                      vocab_size=259, dtype=jnp.float32)
    bad_model = build_model(bad)
    bad_params = bad_model.init(jax.random.PRNGKey(0))
    try:
        ContinuousServingEngine(bad_model, bad_params, max_len=32,
                                max_running=2, page_size=8, mesh=mesh)
        raise SystemExit("expected ValueError for indivisible heads")
    except ValueError as e:
        assert "head" in str(e)
    try:
        ContinuousServingEngine(
            model, params, max_len=32, max_running=2, page_size=8,
            mesh=mesh, policy=Policy(shard_cache_head_dim=False))
        raise SystemExit("expected ValueError for bad policy")
    except ValueError as e:
        assert "head-sharded" in str(e)
    print("TP-VALIDATE-OK")
    """))
