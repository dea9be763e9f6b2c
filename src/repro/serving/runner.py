"""Device-execution layer of the serving stack (``ModelRunner``).

Bottom of the three-layer split (runner / core / async — see
``docs/serving.md`` "Layered architecture"): a :class:`ModelRunner`
owns everything that touches the device for the paged continuous
engine — the per-layer paged KV cache, the compiled prefill / decode
functions and their donation contracts, block-table upload, and the
batched copy-on-write row copier — and knows **nothing** about
scheduling, sequences, arrival times or sampling policy.  Its whole
API is "run this chunk / this decode batch against the cache": the
:class:`~repro.serving.core.EngineCore` turns `Schedule` decisions
into these calls, and anything driving the core (the synchronous
``generate`` driver, the async stepper thread, a test) gets the same
compiled artifacts.

Compilation contracts (moved verbatim from the pre-split engine, so
compile counts and donation behaviour are unchanged):

* ``decode`` compiles **once** per runner: (B, 1) tokens + (B,)
  positions + block tables are all data, so batch membership changes
  never re-specialise XLA;
* ``prefill`` compiles once per (padded chunk bucket, context-page
  bucket) pair — chunk buckets are next-power-of-two lengths with the
  real length a traced scalar;
* the cache argument is **donated** on both, and the paged pool is a
  list of per-layer buffers outside any scan carry (the scan-escape
  layout), so every step is an in-place row scatter costing O(touched
  bytes), not O(pool bytes);
* the CoW copier is one donated gather+scatter over the per-layer
  buffer list, with row plans padded to buckets by the caller.

:class:`BucketRunner` is the same seam for the length-bucket baseline
(``serving.engine.ServingEngine``): per-(batch, prompt-len) prefill +
per-batch decode jits over the ring cache, so both engines sit on one
runner/sampling boundary.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.transformer import Model
from ..obs.trace import (SCOPE_COW_COPY, SCOPE_DECODE, SCOPE_PREFILL,
                         SCOPE_VERIFY)


def _scoped(scope: str, fn):
    """``fn`` traced under ``jax.named_scope(scope)``, so the device
    trace's op metadata names the program's work
    (``repro.obs.trace.PROGRAM_SCOPES``).  The wrapper is a lambda: a
    jitted program keeps the name ``jit__lambda``."""
    def call(args):
        with jax.named_scope(scope):
            return fn(*args)
    return lambda *args: call(args)


def _pad_bucket(n: int, lo: int = 8) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


class ModelRunner:
    """Pure ``(device state, chunk/batch) -> logits`` execution over a
    paged KV cache.  No scheduling knowledge; see module docstring.

    ``mesh=`` switches on the **tensor-parallel mode** (the paper's §3
    partition run over a JAX device mesh, shard ≅ NUMA node): every
    per-layer page-pool buffer is laid out head-sharded over the
    ``model`` axis (``NamedSharding`` on the Hkv dim — each shard holds
    its head slice of *every* page), block tables upload replicated,
    and the compiled decode / prefill / CoW-copy functions run the
    forward inside ``shard_map``: a per-shard **local model** (head
    counts divided by the shard count) attends only over its local
    slice of the pool, and one zero-padded psum per layer
    (``launch.shardings.make_paged_head_merge``) restores the full head
    set before the replicated ``w_o`` — bit-identical maths to the
    single-shard engine, one all-reduce per layer, zero cross-shard
    KV-page traffic.  Donation still aliases each shard's pool buffers
    in place.  ``policy`` (``launch.shardings.Policy``) is validated:
    the TP mode implements the head-sharded cache layout
    (``shard_cache_head_dim``) and requires head counts divisible by
    the mesh's ``model`` axis (§3.2 "partitioned by attention heads").
    """

    def __init__(self, model: Model, params: Any, *, max_running: int,
                 max_len: int, page_size: int, n_pages: int,
                 window_override: Optional[int] = None,
                 mesh: Optional[Any] = None,
                 policy: Optional[Any] = None,
                 quant: Optional[Any] = None,
                 registry: Optional[Any] = None) -> None:
        from ..quant.policy import (QuantPolicy, make_qmm,
                                    quantize_serving_params)
        self.model = model
        self.params = params
        #: serving quantization policy (``repro.quant.QuantPolicy``):
        #: Q4_0 weights are rewritten ONCE here at load — packed codes
        #: and scales are what jit closes over and (in TP mode) what is
        #: device_put per shard — and the model reads them through the
        #: ``qmm`` hook; int8 KV pages are allocated by ``init_cache``
        #: below and quantize/dequantize inside the compiled step.
        self.quant = quant if quant is not None else QuantPolicy()
        if self.quant.weights == "q4":
            self.params = quantize_serving_params(
                params, min_size=self.quant.min_size)
            model.qmm = make_qmm(self.quant.impl)
        self.max_running = max_running
        self.max_len = max_len
        self.page_size = page_size
        self.n_pages = n_pages
        self.max_pages = -(-max_len // page_size)
        self.window_override = window_override
        self.mesh = mesh
        self.tp_axis = "model"
        self.tp_shards = (int(mesh.shape.get(self.tp_axis, 1))
                          if mesh is not None else 1)
        self._c_q4_decode = self._c_q4_prefill = self._c_q4_verify = None
        if registry is not None:
            if self.quant.weights == "q4":
                # dequant dispatch counters: each compiled forward under
                # Q4_0 weights routes every projection through the
                # dequantizing matmul, so count dispatches per phase
                c = registry.counter(
                    "runner.quant.q4_dispatch",
                    "compiled forward dispatches whose projections ran "
                    "through Q4_0 dequantizing matmuls")
                self._c_q4_decode = c.labels(phase="decode")
                self._c_q4_prefill = c.labels(phase="prefill")
                self._c_q4_verify = c.labels(phase="verify")
        self.cache = model.init_cache(max_running, max_len,
                                      page_size=page_size, n_pages=n_pages,
                                      kv_dtype=self.quant.kv_dtype)
        #: (padded chunk len, ctx page bucket) -> compiled prefill;
        #: ctx bucket 0 is the one-shot fresh-sequence path
        self._prefill_jits: Dict[Tuple[int, int], Any] = {}
        #: feed width S -> compiled speculative verify (one per draft
        #: lookahead the engine runs with — in practice a single entry)
        self._verify_jits: Dict[int, Any] = {}
        if mesh is not None:
            self._init_tp(policy)
            return
        self._decode = jax.jit(_scoped(
            SCOPE_DECODE,
            lambda p, c, t, pos: model.decode_step(
                p, c, t, pos, page_size=page_size,
                window_override=window_override)),
            donate_argnums=1)
        # batched CoW page copier over the per-layer buffer list: one
        # donated gather+scatter moves every queued page in-place on
        # every layer (un-jitted .at[].set would copy each buffer once
        # per page); row counts bucket so compiles stay few
        self._copy_rows = jax.jit(_scoped(
            SCOPE_COW_COPY,
            lambda layers, src, dst: jax.tree.map(
                lambda a: a.at[dst].set(a[src]), layers)),
            donate_argnums=0)

    # ------------------------------------------------------------------
    # tensor-parallel mode
    # ------------------------------------------------------------------
    def _init_tp(self, policy: Optional[Any]) -> None:
        from jax import shard_map
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..launch.shardings import (make_paged_head_merge,
                                        paged_cache_specs,
                                        serving_tp_param_specs)

        cfg = self.model.cfg
        mesh, axis, S = self.mesh, self.tp_axis, self.tp_shards
        if policy is not None and not policy.shard_cache_head_dim:
            raise ValueError(
                "TP serving implements the head-sharded KV layout; "
                "Policy(shard_cache_head_dim=False) has no paged variant")
        if cfg.n_heads % S or cfg.n_kv_heads % S:
            raise ValueError(
                f"arch {cfg.name!r}: {cfg.n_heads} query / "
                f"{cfg.n_kv_heads} kv heads do not shard over the "
                f"{S}-way {axis!r} mesh axis (§3.2 partitions by "
                "attention heads)")
        # per-shard local model: head counts divided, head_dim pinned
        # (resolved_head_dim would otherwise re-derive from d_model)
        local_cfg = dataclasses.replace(
            cfg, n_heads=cfg.n_heads // S, n_kv_heads=cfg.n_kv_heads // S,
            head_dim=cfg.resolved_head_dim)
        self.local_model = Model(local_cfg)
        self.local_model.paged_head_merge = make_paged_head_merge(
            cfg.n_heads, S, axis=axis)
        if self.quant.weights == "q4":
            # the per-shard forward reads the same packed/scales leaves,
            # sliced along their column (head) dim by the param specs —
            # Q4_0 quantizes along K, so a column shard of the quantized
            # pair is byte-identical to quantizing the sharded weight
            from ..quant.policy import make_qmm
            self.local_model.qmm = make_qmm(self.quant.impl)

        self._pspecs = serving_tp_param_specs(self.params, axis=axis)
        self._cspecs = paged_cache_specs(self.cache, axis=axis)
        self._repl = NamedSharding(mesh, P())
        # bind params and pool buffers to their shard-local carve-outs
        self.params = jax.device_put(
            self.params, jax.tree.map(
                lambda s: NamedSharding(mesh, s), self._pspecs))
        self.cache = jax.device_put(
            self.cache, jax.tree.map(
                lambda s: NamedSharding(mesh, s), self._cspecs))

        ps, wo = self.page_size, self.window_override
        #: un-jitted shard_map decode — probe with
        #: ``core.tp.collective_ops_in`` (one psum per layer, no
        #: gather/scatter of KV pages)
        self.tp_raw_decode = shard_map(
            lambda p, c, t, pos: self.local_model.decode_step(
                p, c, t, pos, page_size=ps, window_override=wo),
            mesh=mesh, in_specs=(self._pspecs, self._cspecs, P(), P()),
            out_specs=(P(), self._cspecs), check_vma=False)
        self._decode = jax.jit(_scoped(SCOPE_DECODE,
                                       self.tp_raw_decode),
                               donate_argnums=1)
        self._copy_rows = jax.jit(_scoped(
            SCOPE_COW_COPY,
            shard_map(
                lambda layers, src, dst: jax.tree.map(
                    lambda a: a.at[dst].set(a[src]), layers),
                mesh=mesh,
                in_specs=(self._cspecs["layers"], P(), P()),
                out_specs=self._cspecs["layers"], check_vma=False)),
            donate_argnums=0)

    # ------------------------------------------------------------------
    def _prefill_fn(self, padded_len: int, ctx_pages: int):
        key = (padded_len, ctx_pages)
        if key in self._prefill_jits:
            return self._prefill_jits[key]
        if self.mesh is None:
            if ctx_pages:
                self._prefill_jits[key] = jax.jit(_scoped(
                    SCOPE_PREFILL,
                    lambda p, b, c, slot, plen, start:
                    self.model.prefill_paged(
                        p, b, c, slot, plen, start=start,
                        ctx_pages=ctx_pages, page_size=self.page_size,
                        window_override=self.window_override)),
                    donate_argnums=2)
            else:
                self._prefill_jits[key] = jax.jit(_scoped(
                    SCOPE_PREFILL,
                    lambda p, b, c, slot, plen: self.model.prefill_paged(
                        p, b, c, slot, plen, page_size=self.page_size,
                        window_override=self.window_override)),
                    donate_argnums=2)
            return self._prefill_jits[key]
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        ps, wo, local = self.page_size, self.window_override, \
            self.local_model
        if ctx_pages:
            body = (lambda p, b, c, slot, plen, start:
                    local.prefill_paged(
                        p, b, c, slot, plen, start=start,
                        ctx_pages=ctx_pages, page_size=ps,
                        window_override=wo))
            in_specs = (self._pspecs, {"tokens": P()}, self._cspecs,
                        P(), P(), P())
        else:
            body = (lambda p, b, c, slot, plen: local.prefill_paged(
                p, b, c, slot, plen, page_size=ps, window_override=wo))
            in_specs = (self._pspecs, {"tokens": P()}, self._cspecs,
                        P(), P())
        self._prefill_jits[key] = jax.jit(_scoped(
            SCOPE_PREFILL,
            shard_map(body, mesh=self.mesh, in_specs=in_specs,
                      out_specs=(P(), self._cspecs), check_vma=False)),
            donate_argnums=2)
        return self._prefill_jits[key]

    def _verify_fn(self, S: int):
        """Compiled speculative verify for feed width ``S`` (1 + max
        draft tokens).  Same donation contract as decode — the cache
        argument aliases in place — and in TP mode the same shard_map
        wrapping: tokens / positions / feed counts are replicated data,
        the pool stays head-sharded, one psum per layer."""
        fn = self._verify_jits.get(S)
        if fn is not None:
            return fn
        if self.mesh is None:
            fn = jax.jit(_scoped(
                SCOPE_VERIFY,
                lambda p, c, t, pos, nf: self.model.verify_step(
                    p, c, t, pos, nf, page_size=self.page_size,
                    window_override=self.window_override)),
                donate_argnums=1)
        else:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P
            ps, wo, local = self.page_size, self.window_override, \
                self.local_model
            fn = jax.jit(_scoped(
                SCOPE_VERIFY,
                shard_map(
                    lambda p, c, t, pos, nf: local.verify_step(
                        p, c, t, pos, nf, page_size=ps,
                        window_override=wo),
                    mesh=self.mesh,
                    in_specs=(self._pspecs, self._cspecs, P(), P(), P()),
                    out_specs=(P(), self._cspecs), check_vma=False)),
                donate_argnums=1)
        self._verify_jits[S] = fn
        return fn

    def verify(self, fed: np.ndarray, pos: np.ndarray,
               n_fed: np.ndarray) -> jax.Array:
        """One batched speculative verify step: ``fed`` (max_running, S)
        = last sampled token + up to S - 1 draft tokens per lane,
        ``pos`` (max_running,) absolute position of column 0 (-1 = idle
        slot), ``n_fed`` (max_running,) real leading columns per lane.
        Returns (max_running, S, V) — column j's argmax is what plain
        decode would emit after j accepted drafts (see
        ``Model.verify_step``)."""
        logits, self.cache = self._verify_fn(fed.shape[1])(
            self.params, self.cache, jnp.asarray(fed), jnp.asarray(pos),
            jnp.asarray(n_fed))
        if self._c_q4_verify is not None:
            self._c_q4_verify.inc()
        return logits

    def set_block_tables(self, tables: np.ndarray) -> None:
        """Upload the host (max_running, max_pages) block-table array
        (replicated across every shard in TP mode — tables are the
        host-side page map, never sharded)."""
        bt = jnp.asarray(tables)
        if self.mesh is not None:
            bt = jax.device_put(bt, self._repl)
        self.cache["block_tables"] = bt

    def apply_copy_rows(self, src: np.ndarray, dst: np.ndarray) -> None:
        """Apply a ``KVCachePool.copy_row_plan`` to every per-layer
        buffer: whole-page K/V row copies, one compiled dispatch."""
        self.cache = dict(self.cache)
        self.cache["layers"] = self._copy_rows(
            self.cache["layers"], jnp.asarray(src), jnp.asarray(dst))

    def prefill_chunk(self, tokens: Sequence[int], *, slot: int,
                      start: int, fresh: bool) -> jax.Array:
        """Run one prefill chunk (``tokens`` at absolute positions
        ``[start, start + len)``) into batch slot ``slot``; returns the
        chunk's last-token logits.  ``fresh`` selects the cheaper
        one-shot path (nothing resident to attend over)."""
        n = len(tokens)
        padded = _pad_bucket(n)
        toks = np.zeros((1, padded), np.int32)
        toks[0, :n] = tokens
        batch = {"tokens": jnp.asarray(toks)}
        if fresh:
            logits, self.cache = self._prefill_fn(padded, 0)(
                self.params, batch, self.cache,
                jnp.asarray(slot, jnp.int32), jnp.asarray(n, jnp.int32))
        else:
            ctx_pages = min(
                _pad_bucket(-(-(start + n) // self.page_size), lo=1),
                self.max_pages)
            logits, self.cache = self._prefill_fn(padded, ctx_pages)(
                self.params, batch, self.cache,
                jnp.asarray(slot, jnp.int32), jnp.asarray(n, jnp.int32),
                jnp.asarray(start, jnp.int32))
        if self._c_q4_prefill is not None:
            self._c_q4_prefill.inc()
        return logits

    def decode_program_text(self) -> str:
        """HLO text of the compiled decode step, as the device runs it
        (a Pallas kernel shows as ``tpu_custom_call``)."""
        fed = jnp.zeros((self.max_running, 1), jnp.int32)
        pos = jnp.zeros((self.max_running,), jnp.int32)
        return self._decode.lower(self.params, self.cache, fed,
                                  pos).compile().as_text()

    def decode(self, fed: np.ndarray, pos: np.ndarray) -> jax.Array:
        """One batched decode step: ``fed`` (max_running, 1) tokens,
        ``pos`` (max_running,) absolute fed-token positions (-1 = idle
        slot, masked + scratch-paged).  Returns (max_running, 1, V)."""
        logits, self.cache = self._decode(
            self.params, self.cache, jnp.asarray(fed), jnp.asarray(pos))
        if self._c_q4_decode is not None:
            self._c_q4_decode.inc()
        return logits


class BucketRunner:
    """Device seam for the length-bucket baseline: ring-cache prefill +
    lockstep decode jits, one compile per (batch, prompt-len) /
    batch-size respectively."""

    def __init__(self, model: Model, params: Any, *,
                 window_override: Optional[int] = None) -> None:
        self.model = model
        self.params = params
        self._prefill = jax.jit(
            lambda p, b, c: model.prefill(
                p, b, c, window_override=window_override))
        self._decode = jax.jit(
            lambda p, c, t, pos: model.decode_step(
                p, c, t, pos, window_override=window_override))

    def init_cache(self, batch: int, max_len: int, *,
                   cache_len: Optional[int] = None,
                   memory_len: int = 0) -> Dict[str, Any]:
        return self.model.init_cache(batch, max_len, cache_len=cache_len,
                                     memory_len=memory_len)

    def prefill(self, batch: Dict[str, Any], cache: Dict[str, Any]):
        return self._prefill(self.params, batch, cache)

    def decode(self, cache: Dict[str, Any], tokens: jax.Array,
               pos: jax.Array):
        return self._decode(self.params, cache, tokens, pos)
