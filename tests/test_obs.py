"""Observability layer (PR: metrics registry + trace spans + telemetry).

Registry level: counter/gauge/histogram semantics, label sets, quantile
estimates, snapshot schema, Prometheus exposition, NullRegistry no-ops.
Trace level: span reconstruction, JSONL round-trip, lifecycle
validation failure modes.  Engine level: a FakeRunner advances a
``VirtualClock`` by known per-call costs, so ``phase_s``, decode gaps,
the new histograms and ``Completion.t_sched`` are asserted against
hand-computed stamps — admission, chunked prefill and preemption
included.  End-to-end: the real tiny model through the continuous and
async drivers must emit a valid snapshot and a valid trace.
"""

import json

import jax
import jax.numpy as jnp
import pytest

from repro.models import ModelConfig, build_model
from repro.obs import (MetricsRegistry, NullRegistry, NullTracer,
                       RequestTracer, load_jsonl, reconstruct_spans,
                       validate_events, validate_snapshot)
from repro.obs.trace import TraceEvent
from repro.obs.validate import require_gauge
from repro.serving import (AsyncEngine, ContinuousServingEngine,
                           EngineCore, Request, RequestState,
                           SamplingParams, ServingEngine, VirtualClock,
                           throughput_report)


@pytest.fixture(scope="module")
def tiny():
    cfg = ModelConfig(name="tiny", arch_type="dense", n_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                      vocab_size=259, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


# ---------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------
def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("x.count", "help")
    c.inc()
    c.inc(2.5)
    assert c.value() == 3.5
    g = reg.gauge("x.level")
    g.set(7)
    g.set(3)
    assert g.value() == 3.0
    # get-or-create is idempotent; kind mismatch raises
    assert reg.counter("x.count") is c
    with pytest.raises(ValueError):
        reg.gauge("x.count")


def test_labels_are_independent_series():
    reg = MetricsRegistry()
    g = reg.gauge("pool.free")
    b0 = g.labels(node=0, shard=1)
    b1 = g.labels(node=1, shard=1)
    b0.set(5)
    b1.set(9)
    assert g.value(node=0, shard=1) == 5.0
    assert g.value(node=1, shard=1) == 9.0
    # label order does not matter
    assert g.value(shard=1, node=0) == 5.0


def test_histogram_counts_and_quantiles():
    reg = MetricsRegistry()
    h = reg.histogram("lat_ms", buckets=(1.0, 10.0, 100.0))
    b = h.labels()
    for v in (0.5, 2.0, 3.0, 50.0, 500.0):
        b.observe(v)
    s, n = h.value()
    assert n == 5 and s == pytest.approx(555.5)
    # ranks: bucket<=1 has 1, (1,10] has 2, (10,100] has 1, +Inf 1
    assert 0.0 < h.quantile(0.1) <= 1.0
    assert 1.0 < h.quantile(0.5) <= 10.0
    assert h.quantile(0.999) == 100.0       # overflow clamps to top bound
    assert reg.histogram("empty").quantile(0.5) == 0.0


def test_snapshot_schema_and_reset():
    reg = MetricsRegistry()
    reg.counter("a.b").inc(2)
    reg.gauge("c.d").set(1, node=0)
    reg.histogram("e.f").observe(3.0)
    snap = reg.snapshot()
    assert validate_snapshot(snap) == []
    assert json.loads(reg.snapshot_json()) == json.loads(
        json.dumps(snap))   # round-trips through JSON
    names = [c["name"] for c in snap["counters"]]
    assert names == ["a.b"]
    assert snap["gauges"][0]["labels"] == {"node": "0"}
    h = snap["histograms"][0]
    assert sum(h["counts"]) == h["count"] == 1
    reg.reset()
    assert reg.snapshot()["counters"] == []


def test_snapshot_validation_failure_modes():
    assert validate_snapshot([]) != []                  # not an object
    assert any("version" in p for p in validate_snapshot(
        {"version": 99, "counters": [], "gauges": [], "histograms": []}))
    bad_hist = {"version": 1, "counters": [], "gauges": [],
                "histograms": [{"name": "h", "labels": {},
                                "buckets": [1.0], "counts": [1, 0, 0],
                                "sum": 1.0, "count": 1}]}
    assert any("buckets" in p for p in validate_snapshot(bad_hist))


def test_prometheus_exposition():
    reg = MetricsRegistry()
    reg.counter("serving.steps", "engine steps").inc(4)
    h = reg.histogram("d.ms", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    text = reg.to_prometheus()
    assert "# TYPE serving_steps counter" in text
    assert "serving_steps 4" in text
    assert '# HELP serving_steps engine steps' in text
    assert 'd_ms_bucket{le="1"} 1' in text      # cumulative
    assert 'd_ms_bucket{le="10"} 2' in text
    assert 'd_ms_bucket{le="+Inf"} 2' in text
    assert "d_ms_count 2" in text


def test_null_registry_is_inert():
    reg = NullRegistry()
    c = reg.counter("x")
    c.inc(5)
    b = c.labels(node=0)
    b.inc()
    assert c.value() == 0.0
    assert reg.snapshot() == {"version": 1, "counters": [], "gauges": [],
                              "histograms": []}
    assert validate_snapshot(reg.snapshot()) == []


def test_require_gauge():
    reg = MetricsRegistry()
    reg.gauge("kv_pool.pages_free").set(3, node=0, shard=1)
    snap = reg.snapshot()
    assert require_gauge(snap, "kv_pool.pages_free",
                         ["node", "shard"]) == []
    assert require_gauge(snap, "kv_pool.pages_free",
                         ["node", "shard", "rack"]) != []
    assert require_gauge(snap, "nope", []) != []


# ---------------------------------------------------------------------
# trace
# ---------------------------------------------------------------------
def _ev(uid, name, t, **attrs):
    return TraceEvent((uid, name, t, attrs))


def test_span_reconstruction():
    evs = [_ev(1, "QUEUED", 0.0), _ev(1, "PREFILLING", 1.0),
           _ev(1, "PREFILL_CHUNK", 1.0, start=0, n=8),
           _ev(1, "DECODING", 2.0), _ev(1, "FINISHED", 5.0)]
    spans = reconstruct_spans(evs)[1]
    assert spans == [("QUEUED", 0.0, 1.0), ("PREFILLING", 1.0, 2.0),
                     ("DECODING", 2.0, 5.0), ("FINISHED", 5.0, 5.0)]
    assert validate_events(evs) == []


def test_validate_events_failure_modes():
    # non-monotone stamps
    assert any("non-monotone" in p for p in validate_events(
        [_ev(1, "QUEUED", 2.0), _ev(1, "FINISHED", 1.0)]))
    # lifecycle must start at QUEUED
    assert any("starts at" in p for p in validate_events(
        [_ev(1, "DECODING", 0.0), _ev(1, "FINISHED", 1.0)]))
    # nothing after a terminal event
    assert any("after terminal" in p for p in validate_events(
        [_ev(1, "QUEUED", 0.0), _ev(1, "CANCELLED", 1.0),
         _ev(1, "DECODING", 2.0)]))
    # terminal required (unless disabled)
    evs = [_ev(1, "QUEUED", 0.0)]
    assert any("no terminal" in p for p in validate_events(evs))
    assert validate_events(evs, require_terminal=False) == []
    # unknown event name
    assert any("unknown event" in p for p in validate_events(
        [_ev(1, "QUEUED", 0.0), _ev(1, "WAT", 1.0),
         _ev(1, "FINISHED", 2.0)]))


def test_trace_jsonl_round_trip(tmp_path):
    tr = RequestTracer()
    tr.event(3, "QUEUED", 0.25, prompt_len=9)
    tr.event(3, "FINISHED", 1.5, n_tokens=4)
    path = str(tmp_path / "trace.jsonl")
    assert tr.write_jsonl(path) == 2
    back = load_jsonl(path)
    assert back == tr.events()
    assert back[0].attrs == {"prompt_len": 9}
    assert NullTracer().enabled is False


# ---------------------------------------------------------------------
# engine time accounting under VirtualClock (FakeRunner advances the
# clock by known per-call costs, so every stamp is hand-computable)
# ---------------------------------------------------------------------
PREFILL_COST = 0.005
DECODE_COST = 0.002


class FakeRunner:
    """Stands in for ModelRunner: each device call advances the
    VirtualClock by a fixed known cost and returns zero logits (greedy
    -> token 0)."""

    def __init__(self, core, clock):
        self.max_pages = core.runner.max_pages
        self._V = core.model.cfg.vocab_size
        self._B = core.max_running
        self.clock = clock

    def set_block_tables(self, bt):
        pass

    def apply_copy_rows(self, src, dst):
        pass

    def prefill_chunk(self, tokens, *, slot, start, fresh):
        self.clock.advance(PREFILL_COST)
        return jnp.zeros((1, 1, self._V), jnp.float32)

    def decode(self, fed, pos):
        self.clock.advance(DECODE_COST)
        return jnp.zeros((self._B, 1, self._V), jnp.float32)


def _fake_core(tiny, clock, tracer=None, registry=None, **kw):
    _cfg, model, params = tiny
    core = EngineCore(model, params, clock=clock, tracer=tracer,
                      registry=registry, **kw)
    core.runner = FakeRunner(core, clock)
    return core


def _drain(core, clock):
    done = []
    for _ in range(500):
        if not core.has_work():
            break
        done.extend(core.step(clock.now()).finished)
    assert not core.has_work()
    return sorted(done, key=lambda c: c.uid)


def test_phase_and_gap_accounting_matches_hand_stamps(tiny):
    clock = VirtualClock()
    tracer = RequestTracer()
    core = _fake_core(tiny, clock, tracer=tracer, max_len=64,
                      max_running=2, page_size=8, prefix_cache=False)
    sp = SamplingParams(max_new_tokens=3)
    for uid, plen in ((0, 10), (1, 6)):
        core.submit(Request(uid=uid, prompt=list(range(1, plen + 1)),
                            sampling=sp))
    comps = _drain(core, clock)

    # step 1: two one-shot prefills (0.005 each, first token sampled);
    # steps 2-3: batched decodes (0.002 each) -> 3 tokens, done
    assert core.phase_s["prefill_s"] == pytest.approx(2 * PREFILL_COST)
    assert core.phase_s["decode_s"] == pytest.approx(2 * DECODE_COST)
    assert core.decode_gaps_s == pytest.approx([DECODE_COST])

    reg = core.registry
    s, n = reg.histogram("serving.decode.itl_ms").value()
    assert (n, s) == (1, pytest.approx(DECODE_COST * 1e3))
    # both granted a slot by the step at their arrival: no queue wait
    s, n = reg.histogram("serving.queue_wait_ms").value()
    assert (n, s) == (2, 0.0)
    assert reg.counter("serving.tokens.prefill").value() == 16
    assert reg.counter("serving.tokens.decode").value() == 4
    assert reg.counter("scheduler.admissions").value() == 2

    # hand-computed completion stamps: A prefills [0, 0.005],
    # B [0.005, 0.010]; decodes end at 0.012 and 0.014
    a, b = comps
    assert (a.t0, b.t0) == (0.0, 0.0)
    assert a.t_first == pytest.approx(PREFILL_COST)
    assert b.t_first == pytest.approx(2 * PREFILL_COST)
    assert a.t1 == b.t1 == pytest.approx(0.014)
    assert a.t_sched == b.t_sched == 0.0    # admitted at submission
    assert validate_events(tracer.events()) == []

    # reset_run_stats clears the run-scoped series only
    core.reset_run_stats()
    assert core.phase_s == {"prefill_s": 0.0, "decode_s": 0.0}
    assert core.decode_gaps_s == []
    assert reg.histogram("serving.decode.itl_ms").value() == (0.0, 0)
    assert reg.counter("scheduler.admissions").value() == 2  # cumulative


def test_chunked_prefill_chunk_events_and_histogram(tiny):
    clock = VirtualClock()
    tracer = RequestTracer()
    core = _fake_core(tiny, clock, tracer=tracer, max_len=64,
                      max_running=2, page_size=8, prefill_chunk=8,
                      prefix_cache=False)
    core.submit(Request(uid=0, prompt=list(range(1, 21)),
                        sampling=SamplingParams(max_new_tokens=1)))
    _drain(core, clock)

    chunks = [e for e in tracer.events(0) if e.name == "PREFILL_CHUNK"]
    assert [(e.attrs["start"], e.attrs["n"]) for e in chunks] == [
        (0, 8), (8, 8), (16, 4)]
    assert [e.t for e in chunks] == pytest.approx(
        [0.0, PREFILL_COST, 2 * PREFILL_COST])
    assert core.phase_s["prefill_s"] == pytest.approx(3 * PREFILL_COST)
    # one slot grant for the whole chunked prefill, at arrival
    s, n = core.registry.histogram("serving.queue_wait_ms").value()
    assert (n, s) == (1, 0.0)
    names = [e.name for e in tracer.events(0)
             if e.name != "PREFILL_CHUNK"]
    assert names == ["QUEUED", "PREFILLING", "DECODING", "FINISHED"]


def test_preemption_trace_and_counter(tiny):
    # pool sized so two 8-token prompts admit but cannot both grow:
    # page_size 4, 7 usable pages; the youngest (uid 1) gets preempted,
    # requeues, and restarts after uid 0 finishes
    clock = VirtualClock()
    tracer = RequestTracer()
    core = _fake_core(tiny, clock, tracer=tracer, max_len=32,
                      max_running=2, page_size=4, n_pages=8,
                      prefix_cache=False)
    sp = SamplingParams(max_new_tokens=8)
    for uid in (0, 1):
        core.submit(Request(uid=uid, prompt=list(range(1, 9)),
                            sampling=sp))
    comps = _drain(core, clock)

    assert core.registry.counter("scheduler.preemptions").value() >= 1
    victims = {e.uid for e in tracer.events() if e.name == "PREEMPTED"}
    assert victims                          # somebody was preempted...
    for uid in victims:
        names = [e.name for e in tracer.events(uid)]
        i = names.index("PREEMPTED")
        assert "PREFILLING" in names[i:]    # ...and recompute-restarted
        assert names[-1] == "FINISHED"
    assert validate_events(tracer.events()) == []
    assert [len(c.tokens) for c in comps] == [8, 8]


def test_t_sched_decomposes_ttft(tiny):
    # max_running=1 serialises admissions: uid 1 waits for uid 0
    clock = VirtualClock()
    core = _fake_core(tiny, clock, max_len=64, max_running=1,
                      page_size=8, prefix_cache=False)
    sp = SamplingParams(max_new_tokens=3)
    for uid in (0, 1):
        core.submit(Request(uid=uid, prompt=list(range(1, 9)),
                            sampling=sp))
    comps = _drain(core, clock)

    a, b = comps
    assert a.t_sched == 0.0
    # uid 0 runs prefill (0.005) + 2 decodes (0.004) -> finishes (and
    # frees its slot) at 0.009; uid 1 admits on that same step
    assert b.t_sched == pytest.approx(0.009)
    assert b.t_first == pytest.approx(b.t_sched + PREFILL_COST)
    queue_wait = b.t_sched - b.t0
    prefill_wait = b.t_first - b.t_sched
    assert queue_wait + prefill_wait == pytest.approx(b.t_first - b.t0)


def test_cancel_emits_cancelled_event(tiny):
    clock = VirtualClock()
    tracer = RequestTracer()
    core = _fake_core(tiny, clock, tracer=tracer, max_len=64,
                      max_running=2, page_size=8, prefill_chunk=4,
                      prefix_cache=False)
    seq = core.submit(Request(uid=0, prompt=list(range(1, 17)),
                              sampling=SamplingParams(max_new_tokens=4)))
    core.step(0.0)                          # mid-prefill
    assert core.cancel(seq)
    assert [e.name for e in tracer.events(0)] == [
        "QUEUED", "PREFILLING", "PREFILL_CHUNK", "CANCELLED"]
    assert validate_events(tracer.events()) == []
    assert not core.has_work()


def test_pool_gauges_sampled_per_step(tiny):
    clock = VirtualClock()
    core = _fake_core(tiny, clock, max_len=64, max_running=2,
                      page_size=8, n_nodes=2, prefix_cache=False)
    core.submit(Request(uid=0, prompt=list(range(1, 9)),
                        sampling=SamplingParams(max_new_tokens=2)))
    _drain(core, clock)
    snap = core.registry.snapshot()
    assert require_gauge(snap, "kv_pool.pages_free",
                         ["node", "shard"]) == []
    free = {(g["labels"]["node"], g["labels"]["shard"]): g["value"]
            for g in snap["gauges"] if g["name"] == "kv_pool.pages_free"}
    assert set(free) == {("0", "0"), ("1", "0")}
    assert sum(free.values()) == core.pool.n_free() - core.pool.n_retained()


class _SpanRecorder:
    """Stands in for ``TraceAnnotation``: records each span's name and
    stats, those set after it opened included."""

    def __init__(self, spans, name, **stats):
        self.stats = dict(stats)
        spans.append((name, self.stats))

    def set_metadata(self, **stats):
        self.stats.update(stats)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_decode_span_counts_the_pages_the_kernel_reads(tiny, monkeypatch):
    """``serving.decode``'s ``kv_pages`` is the sum over live lanes of
    ``cdiv(pos + 1, page_size)``, beside ``kv_tokens``, the sum of
    ``pos + 1``; idle lanes count nothing."""
    import functools
    from repro.obs.trace import SPAN_DECODE, STEP_SPANS
    from repro.serving import core as core_mod
    spans = []
    monkeypatch.setattr(core_mod, "TraceAnnotation",
                        functools.partial(_SpanRecorder, spans))
    clock = VirtualClock()
    core = _fake_core(tiny, clock, max_len=64, max_running=3,
                      page_size=8, prefix_cache=False)
    # prompts of 10 and 7: the first decode feeds positions 10 and 7
    # (11 and 8 tokens: 2 + 1 pages), the second 11 and 8 (12 and 9
    # tokens: 2 + 2 pages); the third lane stays idle
    for uid, plen in ((0, 10), (1, 7)):
        core.submit(Request(uid=uid, prompt=list(range(1, plen + 1)),
                            sampling=SamplingParams(max_new_tokens=3)))
    _drain(core, clock)
    decodes = [stats for name, stats in spans if name == SPAN_DECODE]
    assert all(set(s) == set(STEP_SPANS[SPAN_DECODE]) for s in decodes)
    assert [(s["lanes"], s["kv_tokens"], s["kv_pages"])
            for s in decodes] == [(2, 19, 3), (2, 21, 4)]


def test_null_registry_disables_engine_metrics(tiny):
    clock = VirtualClock()
    core = _fake_core(tiny, clock, registry=NullRegistry(), max_len=64,
                      max_running=2, page_size=8, prefix_cache=False)
    core.submit(Request(uid=0, prompt=list(range(1, 9)),
                        sampling=SamplingParams(max_new_tokens=2)))
    comps = _drain(core, clock)
    assert len(comps[0].tokens) == 2        # serving still works
    assert core.phase_s == {"prefill_s": 0.0, "decode_s": 0.0}
    assert core.registry.snapshot()["counters"] == []


# ---------------------------------------------------------------------
# throughput_report zero-duration phases (satellite fix)
# ---------------------------------------------------------------------
def test_throughput_report_zero_phases():
    from repro.serving.engine import Completion
    comps = [Completion(uid=0, prompt_len=4, tokens=[1, 2],
                        latency_s=0.0, prefill_s=0.0)]
    rep = throughput_report(comps, wall_s=0.0, prefill_s=0.0,
                            decode_s=0.0)
    assert rep["decode_tok_per_s"] == 0.0   # explicit, not astronomical
    assert rep["prefill_tok_per_s"] == 0.0
    rep = throughput_report(comps, wall_s=2.0, prefill_s=0.5,
                            decode_s=1.5)
    assert rep["decode_tok_per_s"] == pytest.approx(2 / 1.5)


# ---------------------------------------------------------------------
# end-to-end: real model through the drivers
# ---------------------------------------------------------------------
def test_continuous_engine_end_to_end_obs(tiny):
    _cfg, model, params = tiny
    tracer = RequestTracer()
    eng = ContinuousServingEngine(model, params, max_len=64,
                                  max_running=4, page_size=8,
                                  prefill_chunk=8, clock=VirtualClock(),
                                  tracer=tracer)
    reqs = [Request(uid=i, prompt=list(range(1, 12 + i)),
                    sampling=SamplingParams(max_new_tokens=4))
            for i in range(3)]
    comps = eng.generate(reqs, arrivals=[0.0, 0.01, 0.02])
    assert [len(c.tokens) for c in comps] == [4, 4, 4]
    assert validate_snapshot(eng.registry.snapshot()) == []
    assert validate_events(tracer.events()) == []
    for c in comps:
        assert c.t0 <= c.t_sched <= c.t_first <= c.t1
        spans = tracer.spans(c.uid)
        assert [s[0] for s in spans] == ["QUEUED", "PREFILLING",
                                         "DECODING", "FINISHED"]
    text = eng.registry.to_prometheus()
    assert "serving_decode_itl_ms_bucket" in text
    assert "kv_pool_pages_free" in text


def test_bucket_engine_stamps_t_sched(tiny):
    _cfg, model, params = tiny
    eng = ServingEngine(model, params, max_len=32)
    comps = eng.generate(
        [Request(uid=i, prompt=[1, 2, 3, 4],
                 sampling=SamplingParams(max_new_tokens=2))
         for i in range(2)], max_batch=2)
    for c in comps:
        assert c.t_sched == c.t0            # instant admission
    rep = throughput_report(comps, **eng.last_phase_s)
    assert rep["new_tokens"] == 4


@pytest.mark.slow
def test_async_engine_failure_and_obs(tiny):
    _cfg, model, params = tiny
    tracer = RequestTracer()
    eng = AsyncEngine(model, params, max_len=32, max_running=2,
                      page_size=8, tracer=tracer)
    try:
        ok = eng.submit(Request(uid=0, prompt=[1, 2, 3],
                                sampling=SamplingParams(
                                    max_new_tokens=2)))
        bad = eng.submit(Request(uid=1, prompt=list(range(1, 64)),
                                 sampling=SamplingParams(
                                     max_new_tokens=2)))
        comp = eng.result(ok, timeout=120)
        assert len(comp.tokens) == 2 and comp.t_sched >= comp.t0
        with pytest.raises(Exception):
            eng.result(bad, timeout=120)
        assert bad.state is RequestState.FAILED
    finally:
        eng.shutdown()
    assert eng.registry.counter("async.submitted").value() == 2
    assert eng.registry.counter("async.failed").value() == 1
    assert validate_events(tracer.events()) == []
    names = {e.name for e in tracer.events()}
    assert "FAILED" in names and "FINISHED" in names


def test_async_stamps_count_from_submit(tiny):
    """The QUEUED stamp, ``Completion.t0`` and the queue wait all count
    from the client's ``submit``, not from the stepper's inbox drain."""
    _cfg, model, params = tiny
    tracer = RequestTracer()
    eng = AsyncEngine(model, params, max_len=32, max_running=1,
                      page_size=8, tracer=tracer)
    try:
        hs = [eng.submit(Request(uid=0, prompt=[1, 2, 3 + i],
                                 sampling=SamplingParams(
                                     max_new_tokens=3)))
              for i in range(3)]
        comps = [eng.result(h, timeout=120) for h in hs]
    finally:
        eng.shutdown()
    for h, c in zip(hs, comps):
        queued = [e.t for e in tracer.events(h.uid) if e.name == "QUEUED"]
        assert queued == [h.t_submit] == [c.t0]
        assert h.t_scheduled >= h.t_submit
        assert c.t_sched == pytest.approx(h.t_scheduled, abs=1e-9)
    # one lane: each later request got its slot only when the one
    # before it had finished, in submission order
    assert hs[0].t_submit <= hs[1].t_submit <= hs[2].t_submit
    for prev, c in zip(comps, hs[1:]):
        assert c.t_scheduled >= prev.t1
    s, n = eng.registry.histogram("serving.queue_wait_ms").value()
    assert n == 3
    assert s == pytest.approx(
        sum((h.t_scheduled - h.t_submit) * 1e3 for h in hs), abs=1e-6)
    assert validate_events(tracer.events()) == []
