"""``bench/trace_reduce.py`` and the trace-fed readers on a synthesized
``.xplane.pb`` whose answers are reckoned by hand.

Device (ms after t0): programs A [0, 10] (runs the paged kernel),
B [12, 20], C [21, 22]; operations, named by their HLO text as the
chip's trace names them: fusion.1 [0, 4], the kernel [4, 9], fusion.2
[12, 20], argmax [21, 22].  Host spans: the trace window
[0, 25], a decode call [9, 11.5] and a sample [20, 21].
Busy = 9 + 8 + 1 = 18 ms of 25; idle gaps [9, 12] under the decode
call, [20, 21] under the sample, [22, 25] under no span.
"""

import types

import pytest

from bench_tiny import REPO

from bench import registry, trace_reduce

T0 = 1_000_000          # ns
MS = 1_000_000_000      # ps per ms


def _line(lid, name, events, meta):
    body = []
    for nm, s, e in events:
        body.append(f"events {{ metadata_id: {meta[nm]} "
                    f"offset_ps: {int(s * MS)} "
                    f"duration_ps: {int((e - s) * MS)} }}")
    return (f'lines {{ id: {lid} name: "{name}" timestamp_ns: {T0} '
            + " ".join(body) + " }")


def _plane(pid, name, lines):
    names = sorted({nm for _, _, evs in lines for nm, _, _ in evs})
    meta = {nm: i + 1 for i, nm in enumerate(names)}
    body = " ".join(_line(i + 1, ln, evs, meta)
                    for i, (ln, _, evs) in enumerate(lines))
    md = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                  f'name: "{nm}" }} }}' for nm, i in meta.items())
    return f'planes {{ id: {pid} name: "{name}" {body} {md} }}'


KERNEL = ("%paged_gqa_decode_attention.3 = f32[32,8,2,128] "
          "custom-call(s32[32,128] %copy-done.4), "
          "custom_call_target=tpu_custom_call")


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    from jax.profiler import ProfileData
    dev = _plane(1, "/device:TPU:0", [
        ("XLA Modules", None, [("jit__lambda", 0, 10),
                               ("jit__lambda", 12, 20),
                               ("jit_argmax", 21, 22)]),
        ("XLA Ops", None, [("%fusion.1 = bf16[8] fusion(%p)", 0, 4),
                           (KERNEL, 4, 9),
                           ("%fusion.2 = bf16[8] fusion(%q)", 12, 20),
                           ("%argmax = s32[8] reduce(%r)", 21, 22)])])
    sc = _plane(3, "/device:TPU:0 SparseCore 0", [
        ("XLA Ops", None, [("sc.op", 9, 12)])])
    host = _plane(2, "/host:CPU", [
        ("python", None, [("bench.trace_window", 0, 25),
                          ("bench.runner.decode", 9, 11.5),
                          ("bench.sample", 20, 21),
                          ("not.ours", 22, 25)])])
    raw = ProfileData.text_proto_to_serialized_xspace(
        "\n".join([dev, sc, host]))
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(raw)
    return trace_reduce.load(trace_reduce.find_trace(d.parents[2]))


def test_planes_and_spans(trace):
    assert sorted(trace.chips) == [0]          # SparseCore left out
    assert [s.name for s in trace.spans] == [
        "bench.trace_window", "bench.runner.decode", "bench.sample"]


def test_busy_idle_and_ops(trace):
    red = trace_reduce.reduce(trace, [0])
    assert red["window_s"] == pytest.approx(0.025)
    assert red["busy_s"] == pytest.approx(0.018)
    assert red["op_seconds"] == pytest.approx(
        {"fusion": 0.012, "paged_gqa_decode_attention": 0.005,
         "argmax": 0.001})
    assert red["idle_by_span"] == pytest.approx(
        {"bench.runner.decode": 0.003, "bench.sample": 0.001,
         "no host span": 0.003})
    assert trace_reduce.top(red["op_seconds"], 2) == [
        ["fusion", pytest.approx(0.012)],
        ["paged_gqa_decode_attention", pytest.approx(0.005)]]


def test_program_holding_the_kernel(trace):
    chip = trace.chips[0]
    lo, hi = trace_reduce.window(trace)
    mods = trace_reduce.modules_holding(
        chip, r"^%paged_gqa_decode_attention\.\d+ = .*tpu_custom_call",
        lo, hi)
    assert [(m.start - T0, m.end - T0) for m in mods] == [(0, 10e6)]


def test_readers_on_the_trace(trace):
    red = trace_reduce.reduce(trace, [0])
    ctx = {"chip": trace.chips[0], "reduced": red,
           "trace_reduce": trace_reduce}
    read = registry.metric_reader
    assert read("idle_share")(ctx) == pytest.approx(28.0)
    assert read("decode_step_ms")(ctx) == pytest.approx(10.0)
    # prefill: program B only (A runs the kernel, C is not a step
    # program); 8 ms over 400 real tokens dispatched in the window
    spans = types.SimpleNamespace(between=lambda name, a, b: [
        types.SimpleNamespace(info={"n": 300}),
        types.SimpleNamespace(info={"n": 100})])
    ctx.update(spans=spans, trace_window=(0.0, 1.0))
    assert read("prefill_ms_per_ktok")(ctx) == pytest.approx(20.0)


def test_roofline_reader_on_the_trace(trace):
    import json

    from bench import flops, peaks
    conf = json.loads((REPO / "bench" / "configs" / "qwen3-1.7b.json")
                      .read_text())
    spans = types.SimpleNamespace(between=lambda name, a, b: [
        types.SimpleNamespace(info={"kv_lens": [1000, 500]})])
    ctx = {"chip": trace.chips[0], "reduced": trace_reduce.reduce(trace, [0]),
           "trace_reduce": trace_reduce, "flops": flops, "config": conf,
           "peaks": peaks.peaks_for("TPU v5 lite"), "spans": spans,
           "trace_window": (0.0, 1.0)}
    # one decode program ran 5 ms of kernel for lanes at 1,000 and 500
    # live tokens: 1,500 x 114,688 B of K/V plus q (bf16) and out (f32)
    # of 2 lanes x 28 layers x 16 heads x 128, at 819 GB/s
    least = (1500 * 114_688 + 2 * 28 * 16 * 128 * 6) / 819e9
    assert registry.metric_reader("paged_attn_roofline")(ctx) == \
        pytest.approx(100 * least / 0.005)


def test_gaps_and_union():
    assert trace_reduce.union([(5, 8), (0, 2), (1, 3), (9, 20)], 0, 10) \
        == [(0, 3), (5, 8), (9, 10)]
    assert trace_reduce.gaps([(0, 3), (5, 8)], 0, 10) == [(3, 5), (8, 10)]
    assert REPO.is_dir()
