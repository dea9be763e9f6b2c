"""Serving-engine comparison under staggered (Poisson) arrivals.

The experiment behind the continuous-batching subsystem: requests with
mixed prompt lengths arrive as a Poisson process; the length-bucket
baseline can only start once its batch is assembled (and then runs
buckets strictly sequentially), while the continuous engine admits each
request on arrival into the slot-indexed running batch.  Reported rows:

  serving_cb.bucket.*      bucket engine, work starts at the LAST arrival
  serving_cb.continuous.*  paged-KV continuous engine, per-step admission
  serving_cb.speedup       continuous / bucket decode tok/s (>1 = win)

Wall times include the arrival span — that is the point: decode tok/s
here is throughput *as the client sees it*, not device-only.

Two further sections exercise the prefix-caching / chunked-prefill
follow-ons (see ``docs/serving.md``):

  serving_prefix.*   shared-system-prompt Poisson workload, prefix
                     cache off vs on: prefill pages allocated, pages
                     shared, prompt tokens served from cache, and a
                     greedy-token parity check (caching must be
                     invisible in the output)
  serving_chunk.*    long-prompt admission into a busy decode batch,
                     one-shot vs chunked prefill: max wall gap between
                     consecutive decode steps (chunking bounds it)

The async section measures the layered stack's live front-end
(``AsyncEngine``: background stepper thread, lock-guarded inbox)
against the batch-mode driver on the SAME Poisson workload:

  serving_async.ttft_p50_ms / ttft_p99_ms
                     time-to-first-token under open-loop wall-clock
                     submission (client stamps submit, engine stamps
                     the first sampled token)
  serving_async.itl_mean_ms.p50
                     per-request mean inter-token latency, median
                     across requests
  serving_async.batch.ttft_p50_ms / ttft_p99_ms
                     the same arrivals through the synchronous
                     ``generate(arrivals=)`` driver — the async layer
                     must not tax TTFT
  serving_async.greedy_parity
                     async and batch tokens must be identical

The TP section (``serving_tp.*``, see :func:`serving_tp_rows`) runs
the paged engine over ``model``-axis meshes of 1/2/4 shards in a child
process with forced host devices: decode tok/s + TTFT per shard count,
byte-identical greedy parity vs the plain engine, and the collective
budget (one psum per layer, zero KV-page gathers) probed via
``core.tp.collective_ops_in``.

The scan-escape section is the evidence for the per-layer paged-cache
layout (``Model.init_cache`` docstring, docs/serving.md "Cache memory
layout"): per-step cost must be **flat in pool size** at fixed touched
bytes —

  serving_scan_escape.decode_step_ms.pN    compiled decode step, pool
                     swept 64 -> 512 pages (8x), same 4-sequence batch
  serving_scan_escape.prefill_chunk_ms.pN  compiled 16-token resumed
                     prefill chunk over the same sweep
  serving_scan_escape.*_flatness           t(p512) / t(p64), ~1 = flat
  serving_scan_escape.nodonate.*           same decode step WITHOUT
                     buffer donation: XLA must copy every pool buffer
                     per call — the O(pool bytes) behaviour the paged
                     engine escaped (real-model "before" anchor)
  serving_scan_escape.micro.*              XLA microbench of just the
                     cache update: the old stacked-pool-through-
                     lax.scan-carry layout (O(pool bytes) copy floor,
                     scaling ~= pool ratio) vs the per-layer unrolled
                     layout (in-place row scatter, flat)

The quantization section (``serving_quant.*``, see
:func:`serving_quant_rows`) serves the same fixed workload full
precision and under ``--quant q4 --kv-dtype int8``
(``docs/quantization.md``): decode tok/s both ways, the teacher-forced
greedy token-match rate against its documented divergence bound
(``QUANT_MATCH_BOUND``), and the page-capacity rows — bytes per KV
page and whole pages per fixed 16 MiB budget, fp32 vs int8 (the int8
format must fit >= 1.9x the pages).

The HTTP section (``serving_http.*``, see :func:`serving_http_rows`)
drives the full network stack — client HTTP -> ``HttpFrontend`` ->
``Router`` -> engine-worker subprocesses — under a saturating
open-loop Poisson workload of shared-prefix groups, at 1 and 2
replicas: client-side TTFT/ITL percentiles off the socket, aggregate
streamed tok/s, the r2/r1 throughput speedup (2 replicas must win
under saturation given >= 2 cores; on a single-core host the row
measures the oversubscription penalty instead — see
:func:`serving_http_rows`), the prefix-affinity hit rate, and greedy
parity vs an in-process ``AsyncEngine`` on the same prompts (the
wire must be byte-invisible).

The speculative section (``serving_spec.*``, see
:func:`serving_spec_rows`) serves a shared-prefix repetitive-text
Poisson workload with and without ``spec_decode=4`` on a bench-tiny
warm-trained on periodic text: decode tok/s and ITL percentiles both
ways, tokens emitted per lane-step (> 1.0 is the point — every extra
token is a decode forward never run), the draft accept rate, and
greedy byte parity vs k=0 (the acceptance contract).

The SLO section (``serving_slo.*``, see :func:`serving_slo_rows`)
saturates the paged engine with a mixed workload — a deep backlog of
heavy batch requests, latency-sensitive interactive chat, and
"hopeless" heavy requests whose budget can never be met — and serves
it twice: once with every overload-protection knob off (uniform
priority, no deadlines — the pre-SLO engine) and once protected
(interactive priority + deadlines).  Reported per mode: **goodput**
(tokens of completions that met their class's SLO window, per wall
second — tokens served past their deadline are wasted work, not
goodput), interactive TTFT p99, and the protected/unprotected goodput
ratio (the gate metric: protection must not cost goodput at
saturation).  Deadline sheds are counted from ``scheduler.expired``
and greedy byte parity is asserted over requests completed in both
modes (docs/robustness.md).
"""

from __future__ import annotations

import os
import time
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Row = Tuple[str, float, str]


def _setup():
    from repro.models import ModelConfig, build_model
    from repro.serving import Request, SamplingParams

    cfg = ModelConfig(name="bench-tiny", arch_type="dense", n_layers=4,
                      d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
                      vocab_size=259, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    reqs = [Request(uid=i,
                    prompt=list(rng.integers(1, 258, 4 + 4 * (i % 3))),
                    sampling=SamplingParams(max_new_tokens=16))
            for i in range(8)]
    # Poisson process: exponential inter-arrival gaps, mean 0.25 s
    arrivals = np.cumsum(rng.exponential(0.25, size=len(reqs)))
    return model, params, reqs, arrivals.tolist()


def serving_cb_rows(mean_gap_scale: float = 1.0) -> List[Row]:
    from repro.serving import (ContinuousServingEngine, ServingEngine,
                               throughput_report)

    model, params, reqs, arrivals = _setup()
    arrivals = [a * mean_gap_scale for a in arrivals]
    max_len = max(len(r.prompt) for r in reqs) + 16 + 8

    # --- bucket baseline: batching by length needs the whole workload,
    # so the engine cannot start before the last arrival ---
    beng = ServingEngine(model, params, max_len=max_len)
    beng.generate(reqs[:1], max_batch=8)        # warm compile caches
    t0 = time.perf_counter()
    time.sleep(max(arrivals))                   # waiting for arrivals
    bc = beng.generate(reqs, max_batch=8)
    bwall = time.perf_counter() - t0
    brep = throughput_report(bc, wall_s=bwall,
                             prefill_s=beng.last_phase_s["prefill_s"],
                             decode_s=bwall - beng.last_phase_s["prefill_s"])

    # --- continuous engine: admission interleaves with decode ---
    ceng = ContinuousServingEngine(model, params, max_len=max_len,
                                   max_running=8, page_size=8)
    ceng.generate(reqs[:1])                     # warm compile caches
    ceng2 = ContinuousServingEngine(model, params, max_len=max_len,
                                    max_running=8, page_size=8)
    t0 = time.perf_counter()
    cc = ceng2.generate(reqs, arrivals=arrivals)
    cwall = time.perf_counter() - t0
    crep = throughput_report(cc, wall_s=cwall,
                             prefill_s=ceng2.last_phase_s["prefill_s"],
                             decode_s=cwall - ceng2.last_phase_s["prefill_s"])

    speedup = crep["decode_tok_per_s"] / max(brep["decode_tok_per_s"], 1e-9)
    return [
        ("serving_cb.bucket.decode_toks_per_s", bwall * 1e6,
         f"{brep['decode_tok_per_s']:.1f}"),
        ("serving_cb.continuous.decode_toks_per_s", cwall * 1e6,
         f"{crep['decode_tok_per_s']:.1f}"),
        ("serving_cb.continuous.preemptions", 0.0,
         f"{ceng2.scheduler.n_preemptions}"),
        ("serving_cb.speedup", 0.0, f"{speedup:.2f}x"),
    ]


def serving_prefix_rows() -> List[Row]:
    """Shared-system-prompt workload: N requests = one long system
    prompt + a short unique suffix, Poisson arrivals.  Prefix caching
    should cut the pages *allocated* for prefill (matched pages are
    shared, not allocated) without changing a single greedy token."""
    from repro.models import ModelConfig, build_model
    from repro.serving import (ContinuousServingEngine, Request,
                               SamplingParams)

    cfg = ModelConfig(name="bench-tiny", arch_type="dense", n_layers=4,
                      d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
                      vocab_size=259, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(13)
    system = list(rng.integers(1, 258, 64))      # 8 full pages @ ps=8
    reqs = [Request(uid=i, prompt=system + list(rng.integers(1, 258, 8)),
                    sampling=SamplingParams(max_new_tokens=24))
            for i in range(8)]
    arrivals = np.cumsum(rng.exponential(0.08, size=len(reqs))).tolist()
    max_len = len(reqs[0].prompt) + 24 + 8

    results = {}
    for cached in (False, True):
        eng = ContinuousServingEngine(model, params, max_len=max_len,
                                      max_running=8, page_size=8,
                                      prefix_cache=cached)
        eng.generate(reqs[:1])                  # warm compile caches
        for k in eng.pool.stats:
            eng.pool.stats[k] = 0
        comps = eng.generate(reqs, arrivals=arrivals)
        results[cached] = (eng.pool.stats.copy(),
                           [c.tokens for c in comps])
    st_off, toks_off = results[False]
    st_on, toks_on = results[True]
    parity = "OK" if toks_on == toks_off else "MISMATCH"
    saved = st_off["fresh_pages"] - st_on["fresh_pages"]
    return [
        ("serving_prefix.pages_allocated.nocache", 0.0,
         f"{st_off['fresh_pages']}"),
        ("serving_prefix.pages_allocated.cached", 0.0,
         f"{st_on['fresh_pages']}"),
        ("serving_prefix.pages_shared", 0.0, f"{st_on['shared_pages']}"),
        ("serving_prefix.cow_copies", 0.0, f"{st_on['cow_copies']}"),
        ("serving_prefix.prompt_tokens_from_cache", 0.0,
         f"{st_on['cached_tokens']}"),
        ("serving_prefix.pages_saved", 0.0, f"{saved}"),
        ("serving_prefix.greedy_parity", 0.0, parity),
    ]


def serving_chunk_rows() -> List[Row]:
    """Long-prompt admission stall: a 768-token prompt arrives while 4
    requests are mid-decode.  One-shot prefill stalls every decode for
    the whole prompt; chunked prefill (32 tokens/step) interleaves, so
    the max gap between consecutive decode steps stays near one chunk's
    cost.  A wider model than the other sections so prefill *compute*
    (not dispatch overhead) is what stalls the batch."""
    from repro.models import ModelConfig, build_model
    from repro.serving import (ContinuousServingEngine, Request,
                               SamplingParams)

    cfg = ModelConfig(name="bench-wide", arch_type="dense", n_layers=8,
                      d_model=256, n_heads=8, n_kv_heads=4, d_ff=512,
                      vocab_size=259, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(29)
    short = [Request(uid=i, prompt=list(rng.integers(1, 258, 8)),
                     sampling=SamplingParams(max_new_tokens=200))
             for i in range(4)]
    long_r = Request(uid=4, prompt=list(rng.integers(1, 258, 768)),
                     sampling=SamplingParams(max_new_tokens=8))
    arrivals = [0.0] * 4 + [0.15]               # long prompt mid-decode
    max_len = 1024
    # pool sized to the workload's true peak (4 shorts + the long
    # prompt).  Since the scan-escape layout, per-step cost is flat in
    # pool size (see serving_scan_escape below), so this is now just a
    # memory choice — kept at the PR 2 value so anchors stay comparable
    n_pages = 208

    gaps = {}
    for chunk in (None, 32):
        eng = ContinuousServingEngine(model, params, max_len=max_len,
                                      max_running=5, page_size=8,
                                      n_pages=n_pages,
                                      prefill_chunk=chunk,
                                      prefix_cache=False)
        eng.generate([long_r], arrivals=[0.0])  # warm prefill compiles
        eng.generate(short[:1])
        eng.generate(short + [long_r], arrivals=arrivals)   # full warm
        eng.generate(short + [long_r], arrivals=arrivals)
        gaps[chunk] = max(eng.decode_gaps_s) if eng.decode_gaps_s else 0.0
    ratio = gaps[None] / max(gaps[32], 1e-9)
    return [
        ("serving_chunk.max_decode_gap_ms.oneshot", gaps[None] * 1e6,
         f"{gaps[None] * 1e3:.1f}"),
        ("serving_chunk.max_decode_gap_ms.chunked32", gaps[32] * 1e6,
         f"{gaps[32] * 1e3:.1f}"),
        ("serving_chunk.stall_reduction", 0.0, f"{ratio:.2f}x"),
    ]


def _pct(sorted_vals: List[float], q: float) -> float:
    i = min(int(q * len(sorted_vals)), len(sorted_vals) - 1)
    return sorted_vals[i]


def serving_async_rows() -> List[Row]:
    """Open-loop Poisson submission into the live ``AsyncEngine`` vs
    the same workload through the batch-mode driver.  TTFT is what a
    client sees: submit stamped by the caller, first token stamped by
    the engine core (``Completion.t_first``).  Inter-token latency is
    each request's (t1 - t_first) / (n_tokens - 1)."""
    from repro.models import ModelConfig, build_model
    from repro.serving import (AsyncEngine, ContinuousServingEngine,
                               Request, SamplingParams)

    cfg = ModelConfig(name="bench-tiny", arch_type="dense", n_layers=4,
                      d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
                      vocab_size=259, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(23)
    reqs = [Request(uid=i,
                    prompt=list(rng.integers(1, 258, 4 + 4 * (i % 3))),
                    sampling=SamplingParams(max_new_tokens=12))
            for i in range(16)]
    arrivals = np.cumsum(rng.exponential(0.06, size=len(reqs))).tolist()
    max_len = max(len(r.prompt) for r in reqs) + 12 + 8

    # --- batch-mode anchor: the same arrivals, synchronous driver ---
    beng = ContinuousServingEngine(model, params, max_len=max_len,
                                   max_running=8, page_size=8,
                                   prefix_cache=False)
    beng.generate(reqs[:3])                     # warm compile caches
    bcomps = beng.generate(reqs, arrivals=arrivals)
    batch_ttft = sorted(c.t_first - c.t0 for c in bcomps)

    # --- live open-loop submission into the async engine ---
    eng = AsyncEngine(model, params, max_len=max_len, max_running=8,
                      page_size=8, prefix_cache=False)
    warm = [eng.submit(r) for r in reqs[:3]]    # warm the live path
    for h in warm:
        eng.result(h, timeout=300)
    t0 = time.perf_counter()
    handles, t_submit = [], []
    for r, a in zip(reqs, arrivals):
        gap = t0 + a - time.perf_counter()
        if gap > 0:
            time.sleep(gap)
        t_submit.append(time.perf_counter())
        handles.append(eng.submit(r))
    acomps = [eng.result(h, timeout=600) for h in handles]
    eng.shutdown()

    ttft = sorted(c.t_first - ts for c, ts in zip(acomps, t_submit))
    itl = sorted((c.t1 - c.t_first) / max(len(c.tokens) - 1, 1)
                 for c in acomps)
    parity = ("OK" if [c.tokens for c in acomps]
              == [c.tokens for c in bcomps] else "MISMATCH")
    return [
        ("serving_async.ttft_p50_ms", _pct(ttft, 0.5) * 1e6,
         f"{_pct(ttft, 0.5) * 1e3:.1f}"),
        ("serving_async.ttft_p99_ms", _pct(ttft, 0.99) * 1e6,
         f"{_pct(ttft, 0.99) * 1e3:.1f}"),
        ("serving_async.itl_mean_ms.p50", _pct(itl, 0.5) * 1e6,
         f"{_pct(itl, 0.5) * 1e3:.2f}"),
        ("serving_async.batch.ttft_p50_ms", _pct(batch_ttft, 0.5) * 1e6,
         f"{_pct(batch_ttft, 0.5) * 1e3:.1f}"),
        ("serving_async.batch.ttft_p99_ms", _pct(batch_ttft, 0.99) * 1e6,
         f"{_pct(batch_ttft, 0.99) * 1e3:.1f}"),
        ("serving_async.greedy_parity", 0.0, parity),
    ]


def serving_obs_rows() -> List[Row]:
    """Observability overhead gate (``docs/observability.md``): the
    same saturated decode workload served twice — once under
    ``NullRegistry`` + ``NullTracer`` (every instrument call a no-op)
    and once fully instrumented (real registry, real tracer) — must
    agree on decode tok/s within the 3% budget.  The two modes run
    **interleaved** (alternating which goes first each round) and the
    overhead is the minimum of two estimators — the median of
    per-round paired throughput ratios and the best-of-N ceiling
    comparison — because on a shared container either one alone
    false-positives on noise while a real per-token cost registers
    in both (see the comment at the computation).  The throughput
    rows report best-of-round per mode.

      serving_obs.decode_toks_per_s.noop / .instrumented
      serving_obs.overhead_pct     min(paired-median, best-vs-best)
      serving_obs.overhead_budget  OK when overhead_pct <= 3
      serving_obs.trace_events     events the instrumented run recorded
      serving_obs.snapshot_valid   snapshot passes the repro.obs schema
    """
    from repro.obs import (NullRegistry, NullTracer, RequestTracer,
                           validate_events, validate_snapshot)
    from repro.obs.metrics import MetricsRegistry
    from repro.serving import ContinuousServingEngine

    import dataclasses

    model, params, reqs, _arrivals = _setup()
    for r in reqs:                  # saturate: every request at t=0
        r.sampling = dataclasses.replace(r.sampling, max_new_tokens=96)
    max_len = max(len(r.prompt) for r in reqs) + 96 + 8
    REPEATS = 8

    def make(registry, tracer):
        eng = ContinuousServingEngine(
            model, params, max_len=max_len, max_running=8, page_size=8,
            prefix_cache=False, registry=registry, tracer=tracer)
        eng.generate(reqs)          # warm every prefill/decode shape
        return eng

    def timed(eng):
        t0 = time.perf_counter()
        comps = eng.generate(reqs)
        wall = time.perf_counter() - t0
        return sum(len(c.tokens) for c in comps) / wall

    noop_eng = make(NullRegistry(), NullTracer())
    registry, tracer = MetricsRegistry(), RequestTracer()
    eng = make(registry, tracer)
    ratios = []
    noop = instr = 0.0
    for round_ in range(REPEATS):   # alternate modes within each round
        if round_ % 2:              # swap order to cancel position bias
            i = timed(eng)
            n = timed(noop_eng)
        else:
            n = timed(noop_eng)
            i = timed(eng)
        noop, instr = max(noop, n), max(instr, i)
        ratios.append(i / n)        # paired: same round, same drift

    # Two estimators with opposite failure modes, overhead = their
    # minimum.  Median paired ratio: adjacent samples share the same
    # machine state, so their ratio isolates instrumentation cost —
    # but correlated jitter across rounds can still skew the median.
    # Best-vs-best: with contention noise strictly one-sided (the
    # machine only ever slows a sample down), best-of-N per mode
    # converges on each mode's clean ceiling — but a single lucky
    # noop draw can fake an overhead.  A *real* per-token cost (a
    # dict build or lock acquisition inside ``EngineCore.step()``)
    # depresses every instrumented sample and shows up in both.
    ratios.sort()
    mid = len(ratios) // 2
    med = (ratios[mid] if len(ratios) % 2
           else (ratios[mid - 1] + ratios[mid]) / 2.0)
    paired = max((1.0 - med) * 100.0, 0.0)
    ceiling = max((noop - instr) / max(noop, 1e-9) * 100.0, 0.0)
    overhead = min(paired, ceiling)
    snap_ok = not validate_snapshot(registry.snapshot())
    # the warm-up + repeats reuse uids, so lifecycles repeat per uid;
    # validate uid 0's FIRST lifecycle (submit .. FINISHED)
    ev0 = tracer.events(0)
    end = next((i for i, e in enumerate(ev0) if e.name == "FINISHED"),
               None)
    trace_ok = end is not None and not validate_events(ev0[:end + 1])
    return [
        ("serving_obs.decode_toks_per_s.noop", 0.0, f"{noop:.1f}"),
        ("serving_obs.decode_toks_per_s.instrumented", 0.0,
         f"{instr:.1f}"),
        ("serving_obs.overhead_pct", 0.0, f"{overhead:.2f}"),
        ("serving_obs.overhead_budget", 0.0,
         "OK" if overhead <= 3.0 else "OVER"),
        ("serving_obs.trace_events", 0.0, f"{len(tracer.events())}"),
        ("serving_obs.snapshot_valid", 0.0,
         "OK" if snap_ok and trace_ok else "INVALID"),
    ]


def _best_of(fn, *, repeats: int = 3, steps: int = 16) -> float:
    """Best-of-``repeats`` mean seconds per call of ``fn(steps)``."""
    best = float("inf")
    for _ in range(repeats):
        best = min(best, fn(steps) / steps)
    return best


def serving_scan_escape_rows() -> List[Row]:
    """Per-step cost vs pool size at fixed touched bytes.

    Builds the same 4-sequence paged batch (32 resident tokens each)
    over page pools of 64 -> 512 pages and times the compiled decode
    step and a resumed 16-token prefill chunk.  With the per-layer
    scan-escape cache layout both must be flat in pool size; the micro
    pair isolates why — a stacked (L, rows, H, D) pool threaded through
    a ``lax.scan`` carry pays an O(pool bytes) ys copy per call, while
    the unrolled per-layer buffers update in place under donation.
    """
    import functools

    from repro.models import ModelConfig, build_model

    cfg = ModelConfig(name="bench-tiny", arch_type="dense", n_layers=4,
                      d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
                      vocab_size=259, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ps, B, ctx, max_len = 8, 4, 32, 64
    pages_per_slot = ctx // ps + 1          # resident ctx + decode page
    pools = (64, 128, 256, 512)

    def make_cache(n_pages: int):
        cache = model.init_cache(B, max_len, page_size=ps,
                                 n_pages=n_pages)
        bt = np.zeros((B, max_len // ps), np.int32)
        for b in range(B):                  # pages 1.. are real; 0 scratch
            bt[b, :pages_per_slot] = (1 + b * pages_per_slot
                                      + np.arange(pages_per_slot))
        cache["block_tables"] = jnp.asarray(bt)
        return cache

    decode = jax.jit(
        lambda p, c, t, pos: model.decode_step(p, c, t, pos,
                                               page_size=ps),
        donate_argnums=1)
    toks = jnp.ones((B, 1), jnp.int32)
    pos = jnp.full((B,), ctx, jnp.int32)

    def timed_loop(step_fn, state, steps):
        t0 = time.perf_counter()
        for _ in range(steps):
            state = step_fn(state)
            jax.block_until_ready(state)
        return time.perf_counter() - t0

    dec_t = {}
    for P in pools:
        def run(steps, P=P):
            # fresh pool per repeat: the previous repeat donated it away
            logits, c = decode(params, make_cache(P), toks, pos)
            jax.block_until_ready(logits)
            return timed_loop(
                lambda c: decode(params, c, toks, pos)[1], c, steps)

        dec_t[P] = _best_of(run, steps=50)

    # "before" anchor at the real-model level: the same step without
    # donation forces XLA to copy every pool buffer each call, which is
    # the O(pool bytes) floor the stacked scan-carry layout paid too
    decode_nd = jax.jit(
        lambda p, c, t, pos: model.decode_step(p, c, t, pos,
                                               page_size=ps))
    nd_t = {}
    for P in (pools[0], pools[-1]):
        def run(steps, P=P):
            cache = make_cache(P)
            logits, _ = decode_nd(params, cache, toks, pos)
            jax.block_until_ready(logits)
            return timed_loop(
                lambda c: decode_nd(params, c, toks, pos)[1], cache,
                steps)

        nd_t[P] = _best_of(run, steps=50)

    # resumed prefill chunk: 16 tokens at start=16, ctx bucket 8 pages
    prefill = jax.jit(
        lambda p, b, c, slot, plen, start: model.prefill_paged(
            p, b, c, slot, plen, start=start, ctx_pages=8,
            page_size=ps),
        donate_argnums=2)
    chunk = {"tokens": jnp.ones((1, 16), jnp.int32)}
    pf_t = {}
    zero = jnp.asarray(0, jnp.int32)
    sixteen = jnp.asarray(16, jnp.int32)
    for P in (pools[0], pools[-1]):
        def run(steps, P=P):
            logits, c = prefill(params, chunk, make_cache(P), zero,
                                sixteen, sixteen)
            jax.block_until_ready(logits)
            return timed_loop(
                lambda c: prefill(params, chunk, c, zero, sixteen,
                                  sixteen)[1], c, steps)

        pf_t[P] = _best_of(run, steps=32)

    # --- micro pair: cache update alone, carry vs unrolled ---
    L, H, D = cfg.n_layers, cfg.n_kv_heads, cfg.resolved_head_dim
    rows = jnp.arange(B, dtype=jnp.int32) * ps + 1
    newk = jnp.ones((B, H, D), jnp.float32)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def carry_step(pk, pv):
        # the pre-refactor layout: stacked pool as scan xs -> ys forces
        # a fresh O(pool bytes) ys allocation+copy every call
        def body(_, kv):
            k, v = kv
            return None, (k.at[rows].set(newk), v.at[rows].set(newk))
        _, out = jax.lax.scan(body, None, (pk, pv))
        return out

    @functools.partial(jax.jit, donate_argnums=0)
    def unrolled_step(bufs):
        return [(k.at[rows].set(newk), v.at[rows].set(newk))
                for k, v in bufs]

    micro = {}
    for P in (pools[0], pools[-1]):
        shape = (P * ps, H, D)

        def run_carry(steps, shape=shape):
            kv = carry_step(jnp.zeros((L,) + shape, jnp.float32),
                            jnp.zeros((L,) + shape, jnp.float32))
            jax.block_until_ready(kv)
            t0 = time.perf_counter()
            for _ in range(steps):
                kv = carry_step(*kv)
            jax.block_until_ready(kv)
            return time.perf_counter() - t0

        def run_unrolled(steps, shape=shape):
            b = unrolled_step([(jnp.zeros(shape, jnp.float32),
                                jnp.zeros(shape, jnp.float32))
                               for _ in range(L)])
            jax.block_until_ready(b)
            t0 = time.perf_counter()
            for _ in range(steps):
                b = unrolled_step(b)
            jax.block_until_ready(b)
            return time.perf_counter() - t0

        micro[P] = (_best_of(run_carry, steps=32),
                    _best_of(run_unrolled, steps=32))

    lo, hi = pools[0], pools[-1]
    rows_out: List[Row] = []
    for P in pools:
        rows_out.append((f"serving_scan_escape.decode_step_ms.p{P}",
                         dec_t[P] * 1e6, f"{dec_t[P] * 1e3:.3f}"))
    rows_out += [
        ("serving_scan_escape.decode_flatness", 0.0,
         f"{dec_t[hi] / dec_t[lo]:.2f}"),
        (f"serving_scan_escape.nodonate.decode_step_ms.p{lo}",
         nd_t[lo] * 1e6, f"{nd_t[lo] * 1e3:.3f}"),
        (f"serving_scan_escape.nodonate.decode_step_ms.p{hi}",
         nd_t[hi] * 1e6, f"{nd_t[hi] * 1e3:.3f}"),
        ("serving_scan_escape.nodonate.decode_scaling", 0.0,
         f"{nd_t[hi] / max(nd_t[lo], 1e-12):.2f}"),
        (f"serving_scan_escape.prefill_chunk_ms.p{lo}", pf_t[lo] * 1e6,
         f"{pf_t[lo] * 1e3:.3f}"),
        (f"serving_scan_escape.prefill_chunk_ms.p{hi}", pf_t[hi] * 1e6,
         f"{pf_t[hi] * 1e3:.3f}"),
        ("serving_scan_escape.prefill_flatness", 0.0,
         f"{pf_t[hi] / pf_t[lo]:.2f}"),
        (f"serving_scan_escape.micro.carry_ms.p{lo}", micro[lo][0] * 1e6,
         f"{micro[lo][0] * 1e3:.3f}"),
        (f"serving_scan_escape.micro.carry_ms.p{hi}", micro[hi][0] * 1e6,
         f"{micro[hi][0] * 1e3:.3f}"),
        ("serving_scan_escape.micro.carry_scaling", 0.0,
         f"{micro[hi][0] / max(micro[lo][0], 1e-12):.2f}"),
        (f"serving_scan_escape.micro.unrolled_ms.p{lo}",
         micro[lo][1] * 1e6, f"{micro[lo][1] * 1e3:.3f}"),
        (f"serving_scan_escape.micro.unrolled_ms.p{hi}",
         micro[hi][1] * 1e6, f"{micro[hi][1] * 1e3:.3f}"),
        ("serving_scan_escape.micro.unrolled_flatness", 0.0,
         f"{micro[hi][1] / max(micro[lo][1], 1e-12):.2f}"),
    ]
    return rows_out


TP_SHARDS = (1, 2, 4)


def _tp_child() -> None:
    """Child-process body of the ``serving_tp`` section (needs forced
    host devices, which must be set before the first jax import — the
    parent bench process keeps its single real CPU device).  Runs a
    fixed Poisson workload through the paged engine plain and over
    ``model``-axis meshes of every ``TP_SHARDS`` size, and prints one
    JSON dict of measurements to stdout."""
    import json

    from repro.core.tp import collective_ops_in
    from repro.launch.mesh import make_mesh
    from repro.models import ModelConfig, build_model
    from repro.serving import (ContinuousServingEngine, Request,
                               SamplingParams, throughput_report)

    cfg = ModelConfig(name="bench-tp", arch_type="dense", n_layers=4,
                      d_model=128, n_heads=8, n_kv_heads=4, d_ff=256,
                      vocab_size=259, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(17)
    reqs = [Request(uid=i,
                    prompt=list(rng.integers(1, 258, 6 + 4 * (i % 3))),
                    sampling=SamplingParams(max_new_tokens=12))
            for i in range(8)]
    arrivals = np.cumsum(rng.exponential(0.05, size=len(reqs))).tolist()
    max_len = max(len(r.prompt) for r in reqs) + 12 + 8

    def run(mesh=None):
        eng = ContinuousServingEngine(
            model, params, max_len=max_len, max_running=8, page_size=8,
            mesh=mesh)
        eng.generate(reqs[:3])      # warm every prompt-length bucket
        t0 = time.perf_counter()
        comps = eng.generate(reqs, arrivals=arrivals)
        wall = time.perf_counter() - t0
        rep = throughput_report(
            comps, wall_s=wall,
            prefill_s=eng.last_phase_s["prefill_s"],
            decode_s=wall - eng.last_phase_s["prefill_s"])
        ttft = sorted(c.t_first - c.t0 for c in comps)
        return eng, ([c.tokens for c in comps],
                     rep["decode_tok_per_s"],
                     ttft[len(ttft) // 2])

    _, (ref_tokens, *_rest) = run()
    out = {"parity": True}
    for s in TP_SHARDS:
        mesh = make_mesh((s,), ("model",))
        eng, (tokens, toks_per_s, ttft_p50) = run(mesh)
        out[f"s{s}"] = {"decode_toks_per_s": toks_per_s,
                        "ttft_p50_ms": ttft_p50 * 1e3}
        out["parity"] = out["parity"] and tokens == ref_tokens
        if s == TP_SHARDS[-1]:
            r = eng.core.runner
            counts = collective_ops_in(
                r.tp_raw_decode, r.params, r.cache,
                jnp.ones((8, 1), jnp.int32), jnp.zeros((8,), jnp.int32))
            out["psum_per_layer"] = counts.get("psum", 0) / cfg.n_layers
            out["kv_gather_collectives"] = sum(
                v for k, v in counts.items() if k != "psum")
    print(json.dumps(out))


def serving_tp_rows() -> List[Row]:
    """Tensor-parallel paged serving over the ``model`` mesh axis
    (shard ≅ NUMA node, forced host devices): per-shard KV page pools,
    head-sharded paged attention, one psum per layer.

      serving_tp.decode_toks_per_s.sN  continuous decode throughput on
                         the fixed Poisson workload at N shards
      serving_tp.ttft_p50_ms.sN        median time-to-first-token
      serving_tp.greedy_parity         every shard count must produce
                         byte-identical greedy tokens vs the plain
                         single-shard engine
      serving_tp.psum_per_layer        collectives in the compiled
                         decode body (exactly 1 all-reduce per layer)
      serving_tp.kv_gather_collectives non-psum collectives (must be 0:
                         KV-page bytes never cross shards)
    """
    import json
    import os
    import subprocess
    import sys

    if jax.default_backend() != "cpu":
        # a chip belongs to one process: this parent already holds it,
        # so the child could not get it — and its forced host devices
        # would say nothing about the chip anyway
        raise RuntimeError(
            "serving_tp runs its forced-host-device child on CPU hosts "
            f"only (this process holds the {jax.default_backend()})")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count="
                        + str(max(TP_SHARDS)))
    env["PYTHONPATH"] = os.pathsep.join(
        [root, os.path.join(root, "src"),
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    proc = subprocess.run(
        [sys.executable, "-c",
         "from benchmarks.serving_bench import _tp_child; _tp_child()"],
        capture_output=True, text=True, env=env, cwd=root, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(f"serving_tp child failed:\n{proc.stderr[-3000:]}")
    out = json.loads(proc.stdout.strip().splitlines()[-1])

    rows: List[Row] = []
    for s in TP_SHARDS:
        m = out[f"s{s}"]
        rows.append((f"serving_tp.decode_toks_per_s.s{s}", 0.0,
                     f"{m['decode_toks_per_s']:.1f}"))
        rows.append((f"serving_tp.ttft_p50_ms.s{s}",
                     m["ttft_p50_ms"] * 1e3,
                     f"{m['ttft_p50_ms']:.1f}"))
    rows += [
        ("serving_tp.greedy_parity", 0.0,
         "OK" if out["parity"] else "MISMATCH"),
        ("serving_tp.psum_per_layer", 0.0,
         f"{out['psum_per_layer']:.2f}"),
        ("serving_tp.kv_gather_collectives", 0.0,
         f"{out['kv_gather_collectives']}"),
    ]
    return rows


HTTP_GROUPS = 8          # distinct shared 2-block prefixes
HTTP_PER_GROUP = 3       # requests per prefix (2 affinity hits each)
HTTP_MAX_NEW = 16


def _http_workload():
    """Deterministic saturating Poisson workload: 8 shared-prefix
    groups x 3 requests, near-zero inter-arrival gaps (open loop —
    clients do not wait for each other), 16 greedy tokens each."""
    rng = np.random.default_rng(11)
    prompts = []
    for g in range(HTTP_GROUPS):
        prefix = [int(t) for t in
                  rng.integers(1, 250, 32)]          # 2 full 16-blocks
        for j in range(HTTP_PER_GROUP):
            prompts.append(prefix + [251 + g % 8, 1 + j])
    arrivals = np.cumsum(rng.exponential(0.01, size=len(prompts)))
    return prompts, arrivals.tolist()


def _http_poisson_run(n_replicas: int):
    """Serve the workload over the full network stack — client HTTP ->
    ``HttpFrontend`` -> ``Router`` -> worker HTTP -> ``AsyncEngine``
    subprocess — and return per-request timings/tokens + router stats."""
    import http.client as hc
    import json as _json
    import threading

    from repro.serving import HttpFrontend, Router, Supervisor

    prompts, arrivals = _http_workload()
    sup = Supervisor(n_replicas,
                     ["--arch", "tiny", "--max-running", "4"])
    clients = sup.start()
    router = Router(clients, page_size=16)
    sup.on_death = lambda rid, rc: router.mark_dead(rid)
    fe = HttpFrontend(router).start()
    try:
        # compile warm-up: 4 concurrent full-shape requests per replica
        # (keyed to land there), so every prefill shape and decode
        # batch size 1..max_running is compiled on every worker before
        # the clock starts — measured TTFT is serving latency, not XLA
        def _post_blocking(p) -> None:
            conn = hc.HTTPConnection(fe.host, fe.port, timeout=600)
            conn.request("POST", "/v1/completions",
                         _json.dumps({"prompt": p, "max_tokens": 8}),
                         {"Content-Type": "application/json"})
            assert conn.getresponse().read()
            conn.close()

        for rid in clients:
            warm = []
            for s in range(100_000):
                p = [(s * 13 + i) % 250 + 1 for i in range(32)]
                if router.ring.pick(router.affinity_key(p)) == rid:
                    warm.append(p + [253, len(warm)])
                    if len(warm) == 4:
                        break
            ts = [threading.Thread(target=_post_blocking, args=(p,))
                  for p in warm]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
        for c in ("router.affinity.keyed", "router.affinity.hits"):
            inst = router.registry.get(c)
            if inst is not None:
                inst.reset()

        results = [None] * len(prompts)
        t0 = time.perf_counter()

        def run_one(i: int) -> None:
            time.sleep(max(arrivals[i] - (time.perf_counter() - t0), 0))
            conn = hc.HTTPConnection(fe.host, fe.port, timeout=600)
            t_submit = time.perf_counter()
            conn.request("POST", "/v1/completions",
                         _json.dumps({"prompt": prompts[i],
                                      "max_tokens": HTTP_MAX_NEW,
                                      "stream": True}),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            toks, stamps = [], []
            while True:
                line = resp.readline().strip()
                if not line or not line.startswith(b"data:"):
                    continue
                payload = line[5:].strip()
                if payload == b"[DONE]":
                    break
                ev = _json.loads(payload)
                if "token" in ev:
                    toks.append(ev["token"])
                    stamps.append(time.perf_counter())
                elif "error" in ev:
                    raise RuntimeError(f"request {i}: {ev['error']}")
            conn.close()
            results[i] = {"t_submit": t_submit, "stamps": stamps,
                          "tokens": toks}

        threads = [threading.Thread(target=run_one, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        def _count(name: str) -> float:
            inst = router.registry.get(name)
            return inst.value() if inst is not None else 0.0

        keyed = _count("router.affinity.keyed")
        hits = _count("router.affinity.hits")
    finally:
        fe.close()
        router.shutdown()
        sup.shutdown()
    assert all(r is not None and len(r["tokens"]) == HTTP_MAX_NEW
               for r in results), "incomplete HTTP run"
    return {"results": results, "wall": wall, "keyed": keyed,
            "hits": hits, "prompts": prompts}


def serving_http_rows() -> List[Row]:
    """Network serving stack under saturating open-loop Poisson load,
    1 vs 2 engine-worker replicas (``docs/serving.md`` "HTTP serving
    front-end"):

      serving_http.ttft_p50_ms.rN / ttft_p99_ms.rN
                         client-side time-to-first-token over the full
                         wire path (HTTP front door -> router -> worker
                         HTTP -> engine)
      serving_http.itl_p50_ms.rN   median per-request mean inter-token
                         latency off the socket
      serving_http.toks_per_s.rN   aggregate client-visible decode
                         throughput (all streamed tokens / wall)
      serving_http.speedup_r2      r2 / r1 toks_per_s.  On a host with
                         >= 2 cores, 2 replicas must beat 1 under
                         saturation.  On a single-core host (CI
                         containers — see serving_http.host_cpus) the
                         replicas time-slice one core and the row
                         instead quantifies the oversubscription
                         penalty of process replication vs one
                         continuously-batched engine — the paper's
                         argument for a lightweight single-process
                         core, measured
      serving_http.host_cpus       cores visible to this process; the
                         context for reading speedup_r2
      serving_http.affinity_hit_rate.r2
                         keyed requests routed to a replica that
                         already served their prefix (8 groups x 3:
                         2/3 is the deterministic ceiling)
      serving_http.greedy_parity   tokens off the socket vs in-process
                         ``AsyncEngine`` greedy tokens — the network
                         stack must be byte-invisible
    """
    from repro.serving import AsyncEngine, Request, SamplingParams

    runs = {n: _http_poisson_run(n) for n in (1, 2)}

    # in-process reference for the SAME prompts: network serving must
    # not change a single greedy token
    model, params, _, _ = _setup()
    prompts = runs[1]["prompts"]
    with AsyncEngine(model, params,
                     max_len=len(prompts[0]) + HTTP_MAX_NEW + 16,
                     max_running=4, page_size=16) as eng:
        handles = [eng.submit(Request(
            uid=i, prompt=p,
            sampling=SamplingParams(max_new_tokens=HTTP_MAX_NEW)))
            for i, p in enumerate(prompts)]
        ref = [eng.result(h, timeout=600).tokens for h in handles]
    parity = all(runs[n]["results"][i]["tokens"] == ref[i]
                 for n in (1, 2) for i in range(len(prompts)))

    rows: List[Row] = []
    tput = {}
    for n in (1, 2):
        res = runs[n]["results"]
        ttft = sorted((r["stamps"][0] - r["t_submit"]) * 1e3
                      for r in res)
        itl = sorted(float(np.mean(np.diff(r["stamps"])) * 1e3)
                     for r in res)
        tput[n] = sum(len(r["tokens"]) for r in res) / runs[n]["wall"]
        rows += [
            (f"serving_http.ttft_p50_ms.r{n}", ttft[len(ttft) // 2] * 1e3,
             f"{ttft[len(ttft) // 2]:.1f}"),
            (f"serving_http.ttft_p99_ms.r{n}", ttft[-1] * 1e3,
             f"{ttft[-1]:.1f}"),
            (f"serving_http.itl_p50_ms.r{n}", itl[len(itl) // 2] * 1e3,
             f"{itl[len(itl) // 2]:.2f}"),
            (f"serving_http.toks_per_s.r{n}", 0.0, f"{tput[n]:.1f}"),
        ]
    hit_rate = (runs[2]["hits"] / runs[2]["keyed"]
                if runs[2]["keyed"] else 0.0)
    try:
        n_cpus = len(os.sched_getaffinity(0))
    except AttributeError:                        # non-Linux fallback
        n_cpus = os.cpu_count() or 1
    rows += [
        ("serving_http.host_cpus", 0.0, str(n_cpus)),
        ("serving_http.speedup_r2", 0.0, f"{tput[2] / tput[1]:.2f}x"),
        ("serving_http.affinity_hit_rate.r2", 0.0, f"{hit_rate:.2f}"),
        ("serving_http.greedy_parity", 0.0,
         "OK" if parity else "MISMATCH"),
    ]
    return rows


#: documented greedy-divergence bound for the quantized serving path
#: (docs/quantization.md "The divergence gate"): teacher-forced
#: next-token agreement of --quant q4 --kv-dtype int8 vs the fp32
#: engine must stay at or above this on the fixed workload (measured
#: 0.917 at PR 8; the margin absorbs backend numeric drift)
QUANT_MATCH_BOUND = 0.80
#: fixed byte budget the capacity rows size page pools against
QUANT_BUDGET_BYTES = 16 * 1024 * 1024


def serving_quant_rows() -> List[Row]:
    """Quantized serving path vs fp32 (``docs/quantization.md``):
    Q4_0 weights + int8 KV pages through the SAME paged engine on the
    same fixed workload.

      serving_quant.decode_toks_per_s.fp32 / .q4int8
                         continuous-engine decode throughput under the
                         fixed Poisson arrivals, full precision vs
                         --quant q4 --kv-dtype int8
      serving_quant.token_match_rate
                         teacher-forced next-token agreement: the fp32
                         engine's greedy continuations are replayed
                         through the quantized engine one position at a
                         time (prompt + fp32 tokens[:j], max_new=1) and
                         each greedy pick is compared to the fp32 token
                         at that position.  Cascade-free — a flipped
                         token cannot poison later comparisons — so the
                         rate measures per-step quantization error, not
                         trajectory luck.  The replay prompts share
                         pages heavily, so this also exercises
                         prefix-cache sharing + CoW over int8 pages.
      serving_quant.match_budget
                         OK when token_match_rate >= QUANT_MATCH_BOUND
      serving_quant.page_bytes.fp32 / .int8
                         device bytes per KV page (all layers/heads)
      serving_quant.pages_at_16MiB.fp32 / .int8
                         whole pages that fit in the fixed budget
      serving_quant.page_capacity_ratio
                         int8 pages per fp32 page at equal bytes —
                         4*D/(D+4), 3.56x at bench-tiny's D=32; the
                         acceptance floor is 1.9x

    The model is warm-trained briefly (fixed seed, deterministic) so
    greedy argmax has real margins — on random weights every logit gap
    is noise and the match rate measures luck, not quantization.
    """
    from repro.data.pipeline import PackedLMDataset
    from repro.quant.policy import QuantPolicy
    from repro.serving import (ContinuousServingEngine, Request,
                               SamplingParams, throughput_report)
    from repro.training.loop import train
    from repro.training.optimizer import AdamWConfig

    model, params0, reqs, arrivals = _setup()
    ds = PackedLMDataset(seq_len=64, n_docs=500,
                         vocab_size=model.cfg.vocab_size)
    params, _, _ = train(model, params0, ds.batches(8),
                         AdamWConfig(lr=2e-3, warmup_steps=5,
                                     total_steps=80),
                         steps=80, log_every=1000)
    max_new = reqs[0].sampling.max_new_tokens
    max_len = max(len(r.prompt) for r in reqs) + 2 * max_new + 8
    q4int8 = QuantPolicy(weights="q4", kv_dtype="int8")

    def engine(quant):
        return ContinuousServingEngine(
            model, params, max_len=max_len, max_running=8, page_size=8,
            quant=quant)

    def throughput(quant):
        engine(quant).generate(reqs[:1])        # warm compile caches
        eng = engine(quant)
        t0 = time.perf_counter()
        comps = eng.generate(reqs, arrivals=arrivals)
        wall = time.perf_counter() - t0
        rep = throughput_report(
            comps, wall_s=wall,
            prefill_s=eng.last_phase_s["prefill_s"],
            decode_s=wall - eng.last_phase_s["prefill_s"])
        return eng, comps, rep["decode_tok_per_s"]

    feng, fcomps, ftoks = throughput(None)
    qeng, _qcomps, qtoks = throughput(q4int8)

    # teacher-forced replay: every fp32 continuation position becomes
    # its own max_new=1 request against the quantized engine
    one = SamplingParams(temperature=0.0, max_new_tokens=1)
    replay, want = [], []
    for r, c in zip(reqs, fcomps):
        for j in range(len(c.tokens)):
            replay.append(Request(uid=len(replay),
                                  prompt=list(r.prompt) + c.tokens[:j],
                                  sampling=one))
            want.append(c.tokens[j])
    eng = engine(q4int8)
    got = {c.uid: c.tokens for c in eng.generate(replay)}
    match = sum(int(got[u][0] == want[u]) for u in range(len(want)))
    rate = match / len(want)

    pb = {"fp32": feng.pool.cfg.page_bytes,
          "int8": qeng.pool.cfg.page_bytes}
    pages = {k: QUANT_BUDGET_BYTES // v for k, v in pb.items()}
    ratio = pb["fp32"] / pb["int8"]
    return [
        ("serving_quant.decode_toks_per_s.fp32", 0.0, f"{ftoks:.1f}"),
        ("serving_quant.decode_toks_per_s.q4int8", 0.0, f"{qtoks:.1f}"),
        ("serving_quant.token_match_rate", 0.0, f"{rate:.3f}"),
        ("serving_quant.match_budget", 0.0,
         "OK" if rate >= QUANT_MATCH_BOUND else "UNDER"),
        ("serving_quant.page_bytes.fp32", 0.0, f"{pb['fp32']}"),
        ("serving_quant.page_bytes.int8", 0.0, f"{pb['int8']}"),
        ("serving_quant.pages_at_16MiB.fp32", 0.0, f"{pages['fp32']}"),
        ("serving_quant.pages_at_16MiB.int8", 0.0, f"{pages['int8']}"),
        ("serving_quant.page_capacity_ratio", 0.0, f"{ratio:.2f}x"),
    ]


def serving_spec_rows() -> List[Row]:
    """Self-speculative decoding vs plain decode (``docs/serving.md``):
    prompt-lookup drafts + batched paged verify through the SAME
    continuous engine on a shared-prefix + repetitive-text Poisson
    workload — the traffic shape speculation exists for.

      serving_spec.decode_toks_per_s.k0 / .k4
                         decode throughput without / with
                         ``spec_decode=4`` on the same arrivals
      serving_spec.itl_ms.p50.k0 / .k4  (and .p99.*)
                         per-step inter-token latency percentiles from
                         the engines' ``serving.decode.itl_ms``
                         histograms — a verify step costs more wall
                         time than a decode step, but emits up to k+1
                         tokens for it
      serving_spec.tokens_per_step.k0 / .k4
                         tokens emitted per lane per decode/verify
                         step (``serving.tokens.decode`` over the
                         occupancy histogram's lane-step sum) — 1.0 by
                         construction at k=0; > 1.0 is the point of
                         speculation: every extra token is a decode
                         forward the device never ran
      serving_spec.accept_rate
                         accepted / drafted draft tokens over the run
      serving_spec.speedup
                         k4 / k0 decode tok/s
      serving_spec.greedy_parity
                         OK when the k=4 token streams are
                         byte-identical to k=0 — the acceptance
                         contract (also asserted per-scenario in
                         ``tests/test_spec_decode.py``)
      serving_spec.budget
                         OK when parity holds and
                         tokens_per_step.k4 > 1.0

    Warm-trained on PERIODIC text (fixed seed, deterministic): each
    training row tiles a short random pattern, so the model learns to
    continue repetitions — the induction behavior repetitive serving
    traffic exercises and prompt-lookup drafting bets on.  On that
    traffic the drafter's proposals match the model's own greedy
    continuation, acceptance is high, and the verify step's extra cost
    is paid back several tokens at a time.
    """
    from repro.serving import (ContinuousServingEngine, Request,
                               SamplingParams, throughput_report)
    from repro.training.loop import train
    from repro.training.optimizer import AdamWConfig

    model, params0, _reqs, _arr = _setup()
    vocab = model.cfg.vocab_size
    seq_len = 64

    def periodic_batches(batch_size=8, seed=5):
        prng = np.random.default_rng(seed)
        while True:
            rows = []
            for _ in range(batch_size):
                period = int(prng.integers(2, 5))
                pat = prng.integers(1, vocab, size=period)
                row = np.tile(pat, seq_len // period + 2)[:seq_len + 1]
                rows.append(row)
            chunk = np.stack(rows).astype(np.int32)
            yield {"tokens": chunk[:, :-1], "labels": chunk[:, 1:]}

    params, _, _ = train(model, params0, periodic_batches(),
                         AdamWConfig(lr=2e-3, warmup_steps=5,
                                     total_steps=80),
                         steps=80, log_every=1000)

    rng = np.random.default_rng(11)
    system = list(rng.integers(1, 258, 8))      # shared prefix block
    pats = [list(rng.integers(1, 258, 3)) for _ in range(4)]
    reqs = []
    for i in range(8):
        body = pats[i % 4] * 6
        reqs.append(Request(
            uid=i, prompt=system + body[:14 + (i % 3)],
            sampling=SamplingParams(max_new_tokens=24)))
    arrivals = np.cumsum(rng.exponential(0.1, size=len(reqs))).tolist()
    max_len = max(len(r.prompt) for r in reqs) + 24 + 8

    def scrape(eng):
        snap = eng.registry.snapshot()
        hists = {h["name"]: h for h in snap["histograms"]}
        counters = {c["name"]: c["value"] for c in snap["counters"]}
        return (counters.get("serving.tokens.decode", 0.0),
                hists["serving.batch.occupancy"]["sum"], counters,
                hists["serving.decode.itl_ms"])

    def run(k):
        # warm the SAME engine the timed run uses: the verify step's
        # compile (one per draft width) must not land inside the timed
        # window — reqs[0] is repetitive, so a k>0 warmup drafts and
        # compiles it
        eng = ContinuousServingEngine(
            model, params, max_len=max_len, max_running=8,
            page_size=8, spec_decode=k)
        eng.generate(reqs[:2])
        tok0, lane0, _, _ = scrape(eng)
        t0 = time.perf_counter()
        comps = eng.generate(reqs, arrivals=arrivals)
        wall = time.perf_counter() - t0
        rep = throughput_report(
            comps, wall_s=wall,
            prefill_s=eng.last_phase_s["prefill_s"],
            decode_s=wall - eng.last_phase_s["prefill_s"])
        tok1, lane1, counters, itl = scrape(eng)    # run-scoped ITL
        tps = (tok1 - tok0) / max(lane1 - lane0, 1.0)
        return (comps, rep["decode_tok_per_s"], tps,
                itl["p50"], itl["p99"], counters)

    c0, toks0, tps0, p50_0, p99_0, _ = run(0)
    c4, toks4, tps4, p50_4, p99_4, ctr = run(4)
    parity = all(a.tokens == b.tokens for a, b in zip(c0, c4))
    drafted = ctr.get("spec.drafted", 0.0)
    rate = ctr.get("spec.accepted", 0.0) / max(drafted, 1.0)
    return [
        ("serving_spec.decode_toks_per_s.k0", 0.0, f"{toks0:.1f}"),
        ("serving_spec.decode_toks_per_s.k4", 0.0, f"{toks4:.1f}"),
        ("serving_spec.itl_ms.p50.k0", 0.0, f"{p50_0:.2f}"),
        ("serving_spec.itl_ms.p50.k4", 0.0, f"{p50_4:.2f}"),
        ("serving_spec.itl_ms.p99.k0", 0.0, f"{p99_0:.2f}"),
        ("serving_spec.itl_ms.p99.k4", 0.0, f"{p99_4:.2f}"),
        ("serving_spec.tokens_per_step.k0", 0.0, f"{tps0:.2f}"),
        ("serving_spec.tokens_per_step.k4", 0.0, f"{tps4:.2f}"),
        ("serving_spec.accept_rate", 0.0, f"{rate:.3f}"),
        ("serving_spec.speedup", 0.0,
         f"{toks4 / max(toks0, 1e-9):.2f}x"),
        ("serving_spec.greedy_parity", 0.0,
         "OK" if parity else "MISMATCH"),
        ("serving_spec.budget", 0.0,
         "OK" if tps4 > 1.0 and parity else "UNDER"),
    ]


def serving_slo_rows() -> List[Row]:
    """Goodput under SLOs at saturation, protection off vs on.

    The workload holds three request classes over one bench-tiny model
    (4 slots, so the 20-deep heavy backlog saturates the batch):

    * 20 **heavy** requests at t=0 — throughput work, no latency SLO
      (``priority="batch"`` when protected);
    * 8 **interactive** shared-prefix chats arriving just after, SLO =
      2.5x one 4-wide heavy wave's wall time (``W``), deadline-stamped
      when protected;
    * 8 **hopeless** heavies with a half-wave budget that queue-depth
      arithmetic says can never be met — protection must shed them
      from the *queue* (zero compute burned); unprotected they run to
      completion and every token they produce is waste.

    Goodput credits a completion's tokens only when it finished inside
    its class window, so the unprotected run pays twice: hopeless work
    dilutes the denominator (wall) and earns nothing, and interactive
    completions miss their window queued behind the backlog.
    """
    from repro.models import ModelConfig, build_model
    from repro.serving import (ContinuousServingEngine, Request,
                               SamplingParams)

    cfg = ModelConfig(name="bench-tiny", arch_type="dense", n_layers=4,
                      d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
                      vocab_size=259, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(23)
    heavy_prompts = [list(rng.integers(1, 258, 96)) for _ in range(28)]
    system = list(rng.integers(1, 258, 16))     # 2 full pages @ ps=8
    inter_prompts = [system + list(rng.integers(1, 258, 8))
                     for _ in range(8)]
    max_len = 96 + 96 + 8

    # ONE engine serves calibration and both measured runs: compiles
    # (per-engine jit caches) are paid once up front, and with the
    # prefix cache off no KV reuse can leak between the two modes —
    # the pool drains to empty at every generate() boundary.
    eng = ContinuousServingEngine(model, params, max_len=max_len,
                                  max_running=4, page_size=8,
                                  prefix_cache=False)

    # calibrate W = one full 4-wide wave of heavies (the engine's
    # natural service quantum here: 20 queued heavies drain as 5 such
    # waves), post-compile
    def heavies(uids):
        return [Request(uid=u, prompt=heavy_prompts[u],
                        sampling=SamplingParams(max_new_tokens=96))
                for u in uids]

    eng.generate(heavies([0, 1, 2, 3]))
    t0 = time.perf_counter()
    eng.generate(heavies([4, 5, 6, 7]))
    W = time.perf_counter() - t0
    # warm the interactive + mixed-admission shapes too (heavies decode
    # while chats queue, then chats admit at the wave boundary) so
    # neither measured run pays a first-compile stall mid-flight
    warm = heavies([8, 9, 10, 11]) + [
        Request(uid=900 + i, prompt=inter_prompts[i],
                sampling=SamplingParams(max_new_tokens=8))
        for i in range(8)]
    eng.generate(warm, arrivals=[0.0] * 4 + [0.02 * i for i in range(8)])
    # 2.5 waves leaves the interactive window real but meetable:
    # protected they admit at the first wave boundary (priority) and
    # finish inside it; unprotected they queue behind the whole heavy
    # backlog (7 waves) and blow it.  Half a wave can never fit a
    # heavy that must wait waves for a slot — the hopeless class.
    slo = {"heavy": float("inf"), "interactive": 2.5 * W,
           "hopeless": 0.5 * W}

    def workload(protected):
        reqs, arrivals, cls = [], [], {}
        for i in range(20):             # heavy backlog, all at t=0
            reqs.append(Request(
                uid=i, prompt=heavy_prompts[i],
                sampling=SamplingParams(max_new_tokens=96),
                priority="batch" if protected else "interactive"))
            arrivals.append(0.0)
            cls[i] = "heavy"
        for i in range(8):              # hopeless: W/2 budget, 5W queue
            reqs.append(Request(
                uid=100 + i, prompt=heavy_prompts[20 + i],
                sampling=SamplingParams(max_new_tokens=96),
                priority="batch" if protected else "interactive",
                deadline_s=slo["hopeless"] if protected else None))
            arrivals.append(0.01)
            cls[100 + i] = "hopeless"
        for i in range(8):              # interactive chat
            reqs.append(Request(
                uid=200 + i, prompt=inter_prompts[i],
                sampling=SamplingParams(max_new_tokens=8),
                deadline_s=slo["interactive"] if protected else None))
            arrivals.append(0.02 + 0.02 * i)
            cls[200 + i] = "interactive"
        return reqs, arrivals, cls

    results = {}
    for protected in (False, True):
        reqs, arrivals, cls = workload(protected)
        exp0 = eng.registry.get("scheduler.expired").value()
        t0 = time.perf_counter()
        comps = eng.generate(reqs, arrivals=arrivals)
        wall = time.perf_counter() - t0
        good = sum(len(c.tokens) for c in comps
                   if c.t1 - c.t0 <= slo[cls[c.uid]])
        ttft = sorted(c.t_first - c.t0 for c in comps
                      if cls[c.uid] == "interactive")
        expired = eng.registry.get("scheduler.expired").value() - exp0
        results[protected] = {
            "goodput": good / wall, "wall": wall, "expired": expired,
            "ttft": ttft, "tokens": {c.uid: list(c.tokens)
                                     for c in comps}}
    un, pr = results[False], results[True]
    ratio = pr["goodput"] / max(un["goodput"], 1e-9)
    # greedy byte parity over requests completed in BOTH modes: the
    # SLO layer may drop requests, never change a survivor's tokens
    both = set(un["tokens"]) & set(pr["tokens"])
    parity = "OK" if all(un["tokens"][u] == pr["tokens"][u]
                         for u in both) else "MISMATCH"
    return [
        ("serving_slo.calib_wave_wall_ms", W * 1e6, f"{W * 1e3:.0f}"),
        ("serving_slo.goodput_toks_per_s.unprotected",
         un["wall"] * 1e6, f"{un['goodput']:.1f}"),
        ("serving_slo.goodput_toks_per_s.protected",
         pr["wall"] * 1e6, f"{pr['goodput']:.1f}"),
        ("serving_slo.goodput_ratio", 0.0, f"{ratio:.2f}x"),
        ("serving_slo.interactive_ttft_p99_ms.unprotected",
         _pct(un["ttft"], 0.99) * 1e6 if un["ttft"] else 0.0,
         f"{_pct(un['ttft'], 0.99) * 1e3:.0f}" if un["ttft"] else "n/a"),
        ("serving_slo.interactive_ttft_p99_ms.protected",
         _pct(pr["ttft"], 0.99) * 1e6 if pr["ttft"] else 0.0,
         f"{_pct(pr['ttft'], 0.99) * 1e3:.0f}" if pr["ttft"] else "n/a"),
        ("serving_slo.deadline_sheds.protected", 0.0,
         f"{pr['expired']:.0f}"),
        ("serving_slo.completed.unprotected", 0.0,
         f"{len(un['tokens'])}"),
        ("serving_slo.completed.protected", 0.0,
         f"{len(pr['tokens'])}"),
        ("serving_slo.greedy_parity", 0.0, parity),
    ]


def all_rows() -> List[Row]:
    return (serving_cb_rows() + serving_prefix_rows() +
            serving_chunk_rows() + serving_async_rows() +
            serving_obs_rows() + serving_scan_escape_rows() +
            serving_tp_rows() + serving_http_rows() +
            serving_quant_rows() + serving_spec_rows() +
            serving_slo_rows())


if __name__ == "__main__":
    for name, us, derived in all_rows():
        print(f"{name},{us:.1f},{derived}")
