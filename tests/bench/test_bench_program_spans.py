"""``bench/program_spans.py`` and the readers built on the program's own
spans: on a synthesized ``.xplane.pb`` whose answers are reckoned by
hand, and on a real CPU profiler trace of a tiny ``AsyncEngine``.

Synthesized trace (ms after t0).  Device operations [2, 9], [12, 20],
[24, 28] in the trace window [0, 30]: idle gaps [0, 2], [9, 12],
[20, 24], [28, 30].  Program spans on the stepper's line: admit [0, 1.5];
a step [1.5, 21] holding schedule [1.5, 1.8], decode [2, 8] and sample
[8, 11]; publish [21, 23]; a step [23, 29]; and a step [31, 33] past
the window.  The gaps' midpoints fall under admit (2 ms), sample
(3 ms), publish (4 ms) and the second step (2 ms).
"""

import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import REPO  # noqa: F401  (puts the repo on sys.path)

from bench import program_spans, registry, trace_reduce

T0 = 1_000_000          # ns
MS = 1_000_000_000      # ps per ms

STEP_1 = {"prefills": 1, "lanes": 3, "queued": 0, "pages_used": 300,
          "pages_reserved": 1000}
STEP_2 = dict(STEP_1, prefills=0, queued=2, pages_used=500)
HOST = [("bench.trace_window", 0, 30, {}),
        ("serving.admit", 0, 1.5, {"n_new": 2, "n_cancel": 0}),
        ("serving.step", 1.5, 21, STEP_1),
        ("serving.schedule", 1.5, 1.8, {}),
        ("serving.decode", 2, 8, {"lanes": 3, "kv_tokens": 900}),
        ("serving.sample", 8, 11, {}),
        ("serving.publish", 21, 23, {"tokens": 3}),
        ("serving.step", 23, 29, STEP_2),
        ("serving.step", 31, 33, dict(STEP_2, pages_used=1000))]


def _plane(pid, name, lines):
    """``lines``: (line name, [(event name, start ms, end ms, stats)])."""
    names = sorted({ev[0] for _, evs in lines for ev in evs})
    meta = {nm: i + 1 for i, nm in enumerate(names)}
    stat_names = sorted({k for _, evs in lines for ev in evs
                         for k in ev[3]})
    smeta = {nm: i + 1 for i, nm in enumerate(stat_names)}
    body = []
    for lid, (ln, evs) in enumerate(lines, 1):
        out = []
        for nm, s, e, stats in evs:
            st = " ".join(f"stats {{ metadata_id: {smeta[k]} "
                          f"int64_value: {v} }}" for k, v in stats.items())
            out.append(f"events {{ metadata_id: {meta[nm]} "
                       f"offset_ps: {int(s * MS)} "
                       f"duration_ps: {int((e - s) * MS)} {st} }}")
        body.append(f'lines {{ id: {lid} name: "{ln}" timestamp_ns: {T0} '
                    + " ".join(out) + " }")
    md = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                  f'name: "{nm}" }} }}' for nm, i in meta.items())
    sm = " ".join(f'stat_metadata {{ key: {i} value {{ id: {i} '
                  f'name: "{nm}" }} }}' for nm, i in smeta.items())
    return f'planes {{ id: {pid} name: "{name}" {" ".join(body)} {md} {sm} }}'


def _write(root, planes):
    from jax.profiler import ProfileData
    raw = ProfileData.text_proto_to_serialized_xspace("\n".join(planes))
    d = root / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(raw)
    return root


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    ops = [(f"%fusion.{i} = bf16[8] fusion(%p)", s, e, {})
           for i, (s, e) in enumerate([(2, 9), (12, 20), (24, 28)])]
    dev = _plane(1, "/device:TPU:0",
                 [("XLA Modules", [("jit__lambda", 2, 28, {})]),
                  ("XLA Ops", ops)])
    host = _plane(2, "/host:CPU", [("engine-stepper", HOST)])
    return _write(tmp_path_factory.mktemp("trace"), [dev, host])


@pytest.fixture
def ctx(traced, monkeypatch):
    monkeypatch.setattr(program_spans, "TRACE_DIR", traced)
    tr = trace_reduce.load(trace_reduce.find_trace(traced))
    return {"chip": tr.chips[0], "reduced": trace_reduce.reduce(tr, [0]),
            "trace_reduce": trace_reduce}


def test_loads_program_spans_with_stats(traced):
    spans = program_spans.find(traced)
    assert [s.name for s in spans] == [h[0] for h in HOST[1:]]
    assert [s.stats for s in spans] == [h[3] for h in HOST[1:]]
    step = spans[1]
    assert (step.start - T0, step.end - T0) == (1.5e6, 21e6)
    lo, hi = T0, T0 + 30e6
    assert [s.stats for s in program_spans.inside(
        spans, program_spans.STEP, lo, hi)] == [STEP_1, STEP_2]


def test_kv_pages_used_share_reader(ctx):
    # the two steps inside the window: 300 and 500 of 1,000 pages
    assert registry.metric_reader("kv_pages_used_share")(ctx) == \
        pytest.approx(40.0)


def test_frontend_idle_share_reader(ctx):
    # 2 ms under admit and 4 ms under publish, of a 30 ms window
    assert registry.metric_reader("frontend_idle_share")(ctx) == \
        pytest.approx(20.0)
    idle = trace_reduce.idle_by_span(
        ctx["chip"], program_spans.find(), *ctx["reduced"]["window"])
    assert idle == pytest.approx({"serving.admit": 0.002,
                                  "serving.sample": 0.003,
                                  "serving.publish": 0.004,
                                  "serving.step": 0.002})


def test_span_readers_read_nothing_without_program_spans(tmp_path,
                                                         monkeypatch):
    """A program that opens no ``serving.*`` span (or no trace at all)
    reads nothing, and nothing raises."""
    dev = _plane(1, "/device:TPU:0",
                 [("XLA Ops", [("%fusion.1 = f32[1] fusion()", 0, 5, {})])])
    host = _plane(2, "/host:CPU",
                  [("python", [("bench.trace_window", 0, 10, {})])])
    root = _write(tmp_path, [dev, host])
    tr = trace_reduce.load(trace_reduce.find_trace(root))
    c = {"chip": tr.chips[0], "reduced": trace_reduce.reduce(tr, [0]),
         "trace_reduce": trace_reduce}
    for where in (root, tmp_path / "absent"):
        monkeypatch.setattr(program_spans, "TRACE_DIR", where)
        assert program_spans.find() == []
        for name in ("kv_pages_used_share", "frontend_idle_share"):
            assert registry.metric_reader(name)(c) is None


def _rec(due, t_submit=None, t_scheduled=None, stamped=True):
    h = types.SimpleNamespace(t_scheduled=t_scheduled)
    if stamped:
        h.t_submit = t_submit
    return types.SimpleNamespace(due=due, handle=h)


def test_queue_wait_p95_reader():
    read = registry.metric_reader("queue_wait_p95_ms")
    recs = [_rec(1.0 + i, 1.0 + i, 1.0 + i + 0.01 * i) for i in range(9)]
    recs.append(_rec(50.0, 50.0, 90.0))     # due after the window
    ctx = {"window": (0.0, 10.0), "records": recs}
    assert read(ctx) == pytest.approx(
        float(np.percentile([0.01 * i for i in range(9)], 95)) * 1e3)
    # never granted a slot: counts with its wait so far
    t = time.perf_counter()
    ctx["records"] = recs + [_rec(9.5, t - 5.0)]
    waits = [0.01 * i for i in range(9)] + [5.0]
    assert read(ctx) >= float(np.percentile(waits, 95)) * 1e3
    # a program without the stamps reads nothing
    ctx["records"] = [_rec(1.0, stamped=False)]
    assert read(ctx) is None
    ctx["records"] = []
    assert read(ctx) is None


# ---------------------------------------------------------------------
# the real program under the CPU profiler
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A tiny engine serves, under one profiler session: a chunked
    prompt, then one that shares its first 12 tokens (a copy-on-write
    of the half-shared page), then — with speculative decoding on — a
    prompt holding every token id, so every step drafts and verifies."""
    from repro.models import ModelConfig, build_model
    from repro.serving import AsyncEngine, Request, SamplingParams
    cfg = ModelConfig(name="tiny", arch_type="dense", n_layers=2,
                      d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                      vocab_size=32, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    root = tmp_path_factory.mktemp("profile")
    kw = dict(max_len=64, max_running=2, page_size=8)
    a = Request(uid=0, prompt=list(range(1, 17)),
                sampling=SamplingParams(max_new_tokens=3))
    b = Request(uid=0, prompt=list(range(1, 13)) + [29, 30, 31],
                sampling=SamplingParams(max_new_tokens=2))
    c = Request(uid=0, prompt=list(range(32)),
                sampling=SamplingParams(max_new_tokens=4))
    with jax.profiler.trace(str(root)):
        with AsyncEngine(model, params, prefill_chunk=8, **kw) as eng:
            for r in (a, b):
                eng.result(eng.submit(r), timeout=300)
            n_pages = eng.core.n_pages
        with AsyncEngine(model, params, spec_decode=2, **kw) as eng:
            eng.result(eng.submit(c), timeout=300)
    return program_spans.find(root), n_pages


def test_engine_spans_under_the_cpu_profiler(served):
    from repro.obs.trace import STEP_SPANS
    spans, n_pages = served
    assert {s.name for s in spans} == set(STEP_SPANS)
    steps = [s for s in spans if s.name == program_spans.STEP]
    for s in spans:
        if s.name in ("serving.decode", "serving.sample",
                      "serving.sync_tables"):
            assert any(p.start <= s.start and s.end <= p.end
                       for p in steps), s
    for s in steps:
        assert set(s.stats) == set(STEP_SPANS[program_spans.STEP])
        assert s.stats["pages_reserved"] == n_pages - 1
        assert 0 <= s.stats["pages_used"] <= n_pages - 1
    # the first request's prompt in two 8-token chunks
    chunks = [(s.stats["start"], s.stats["n"])
              for s in spans if s.name == "serving.prefill_chunk"]
    assert chunks[:2] == [(0, 8), (8, 8)]
    # its two decode steps: one lane over 17, then 18 positions
    decodes = [(s.stats["lanes"], s.stats["kv_tokens"])
               for s in spans if s.name == "serving.decode"]
    assert decodes[:2] == [(1, 17), (1, 18)]
    first = next(s for s in steps if s.stats["lanes"] == 1)
    assert first.stats["pages_used"] >= 3      # 17 tokens in 8-token pages
    admits = [s for s in spans if s.name == program_spans.ADMIT]
    assert sum(s.stats["n_new"] for s in admits) == 3
