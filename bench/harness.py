"""One run of one cell: set up, warm up, measure a window, check.

``run`` builds the engine users call (``AsyncEngine``) over the
configuration's model with weights from the seed, serves one warm-up
request of every prompt length the cell's traffic can send, opens the
window, drives the traffic for ``seconds``, and then — with the
program's state freed — compares a sample of what it served with the
plain reference.  End-to-end metrics are taken on the client's side
from the stamps of ``bench/driver.py``; with ``trace`` the profiler
records a few seconds in the middle of the window and the per-layer
readers take their numbers from that trace, the host spans and the
program's counters.
"""

from __future__ import annotations

import gc
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import check, driver, flops, registry, spans as bench_spans
from bench import traffic as bench_traffic
from bench import trace_reduce, weights
from bench.model import build
from bench.peaks import peaks_for

#: seconds profiled in the middle of a traced window
TRACE_SECONDS = 4.0
#: how long after the window a request may take to deliver its first
#: token before it counts as failed
FIRST_TOKEN_GRACE_S = 60.0


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class CompileCount:
    """Programs compiled or loaded from the persistent cache, with the
    time each was recorded (``jax.monitoring``)."""

    def __init__(self) -> None:
        import jax
        self.at: List[tuple] = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _dur(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.at.append((time.perf_counter(), kw.get("fun_name", "?")))

    def between(self, t0: float, t1: float) -> List[str]:
        return [name for t, name in self.at if t0 <= t <= t1]


def _pct(xs, q) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, float), q)) if len(xs) else None


def _occupancy(engine):
    h = engine.core.registry.get("serving.batch.occupancy")
    return h.value() if h is not None else (0.0, 0)


def _check_layout(model, params) -> None:
    """The seeded tree must be exactly the tree the program takes."""
    import jax
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if got != want:
        raise ValueError(f"seeded weight tree {got} differs from the "
                         f"program's {want}")


def end_to_end(recs, t_open: float, t_close: float, seconds: float,
               t_wait_end: float) -> Dict[str, float]:
    """Client-side metrics of the window: requests due (open loop) or
    sent (closed loop) in it, tokens delivered in it, and every gap
    between two tokens of one request delivered in it."""
    due = [r for r in recs if t_open <= r.due < t_close]
    ttft = [(r.stamps[0] if r.stamps else t_wait_end) - r.due for r in due]
    stamps_in = [[t for t in r.stamps if t_open <= t <= t_close]
                 for r in recs]
    itl = [b - a for s in stamps_in for a, b in zip(s, s[1:])]
    return {
        "attempted": len(due),
        "failed": sum(1 for r in due if r.failed or not r.stamps),
        "tokens_per_s": sum(map(len, stamps_in)) / seconds,
        "ttft_p95_ms": None if not ttft else _pct(ttft, 95) * 1e3,
        "itl_p95_ms": None if not itl else _pct(itl, 95) * 1e3,
        "n_itl": len(itl),
    }


class Session:
    """The engine of one cell with weights from one seed, set up and
    warmed for the plans it will serve."""

    def __init__(self, cell: registry.Cell, *, seed: int, plans,
                 trace: bool) -> None:
        import jax

        from repro.serving import AsyncEngine
        self.cell, self.conf = cell, cell.config
        self.compiles = CompileCount()
        self.model = build(cell.config_name, self.conf)
        self.params = weights.make(self.conf, seed)
        jax.block_until_ready(self.params)
        _check_layout(self.model, self.params)
        t_weights = time.perf_counter()
        eng = cell.engine
        self.engine = AsyncEngine(
            self.model, self.params, max_len=int(eng["max_len"]),
            max_running=int(eng["max_running"]),
            page_size=int(eng["page_size"]),
            prefill_chunk=eng.get("prefill_chunk"),
            seed=weights.seed32(seed, "engine"))
        self.spans = bench_spans.Spans()
        self._records: List = []
        if trace:
            bench_spans.instrument(self.engine, self.spans)
        lengths = sorted({n for p in plans for n in p.prompt_lengths})
        warm = bench_traffic.warmup_prompts(lengths, seed,
                                            int(self.conf["vocab_size"]))
        driver.serve_all(self.engine, warm, max_new=2)
        say(f"warm-up: {len(warm)} prompts, one of every length the "
            f"traffic sends, in {time.perf_counter() - t_weights:.3f} s")

    def set_weights(self, seed: int) -> None:
        """Serve another seed's weights with the same programs."""
        self.params = weights.make(self.conf, seed)
        self.engine.core.params = self.params
        self.engine.core.runner.params = self.params

    def measure(self, plan, seconds: float, *, trace_dir=None) -> Dict:
        """Drive ``plan`` for a window of ``seconds``; with
        ``trace_dir`` profile a few seconds in its middle."""
        import jax
        drv = driver.Driver(self.engine, plan)
        drv.start()
        if plan.loop == "closed":
            first = list(drv.records)
            while not all(r.done or r.failed for r in first):
                if drv.error is not None:
                    drv.stop()
                time.sleep(0.005)
            say(f"ramp: {plan.clients} clients out of phase after "
                f"{time.perf_counter() - drv.t0:.3f} s")
        t_open = time.perf_counter() if plan.loop == "closed" else drv.t0
        occ0 = _occupancy(self.engine)
        marks = {}
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
            t_tr = t_open + max(0.0, (seconds - TRACE_SECONDS) / 2)
            time.sleep(max(0.0, t_tr - time.perf_counter()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # host spans only: the
            opts.host_tracer_level = 2      # Python tracer slows the host
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                marks["lo"] = time.perf_counter()
                time.sleep(min(TRACE_SECONDS, seconds))
                marks["hi"] = time.perf_counter()
            jax.profiler.stop_trace()
        t_close = t_open + seconds
        time.sleep(max(0.0, t_close - time.perf_counter()))
        drv.stop()
        occ1 = _occupancy(self.engine)
        drv.wait_first_tokens([r for r in drv.records if r.due < t_close],
                              FIRST_TOKEN_GRACE_S)
        t_wait_end = time.perf_counter()
        self._records += drv.records
        inside = self.compiles.between(t_open, t_close)
        n_compiles = len(inside)
        say(f"programs compiled or loaded inside the window: {n_compiles} "
            f"{inside}")
        return {"records": drv.records, "t_open": t_open,
                "t_close": t_close, "t_wait_end": t_wait_end,
                "marks": marks, "n_compiles": n_compiles,
                "occupancy": (occ1[0] - occ0[0], occ1[1] - occ0[1]),
                "e2e": end_to_end(drv.records, t_open, t_close, seconds,
                                  t_wait_end)}

    def cancel(self, records) -> None:
        """Tear down what is still in flight (between windows)."""
        live = [r.handle for r in records if not r.handle.done]
        for h in live:
            self.engine.cancel(h)
        while any(not h.done for h in live):
            time.sleep(0.005)

    def close(self) -> None:
        """Stop the engine and drop the program's device state.  The
        records outlive it, so their token callbacks, which lead back to
        the engine through the driver, go first."""
        self.engine.shutdown()
        for r in self._records:
            r.handle.on_token = None
        self.engine = self.params = self._records = None
        gc.collect()


def sample_and_compare(cell, seed: int, records, *, params=None,
                       control: bool = False) -> Dict:
    eng, mix = cell.engine, cell.traffic
    picked = check.sample([r for r in records if r.done], seed,
                          tokens=int(cell.check["tokens"]),
                          chunk=int(eng.get("prefill_chunk")
                                    or eng["max_len"]))
    t_ref = time.perf_counter()
    out = check.compare(cell.config, seed, picked, params=params,
                        width=int(eng["max_len"]),
                        rows=int(mix["output"]["max"]), control=control)
    say(f"reference over {out['requests']} requests, {out['tokens']} "
        f"served tokens in {time.perf_counter() - t_ref:.3f} s")
    return out


def run(cell: registry.Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, repo: Path = registry.REPO, devices=None) -> Dict:
    import jax
    devices = devices or jax.devices()[:cell.chips]
    plan = bench_traffic.build(cell.traffic, seed, seconds,
                               int(cell.config["vocab_size"]))
    say(f"traffic {cell.traffic_name}: {bench_traffic.summary(plan)}")
    sess = Session(cell, seed=seed, plans=[plan], trace=trace)
    trace_dir = repo / ".bench_trace" if trace else None
    w = sess.measure(plan, seconds, trace_dir=trace_dir)
    setup_s = w["t_open"] - t_start
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    spans = sess.spans
    sess.close()
    cmp_ = sample_and_compare(cell, seed, w["records"])
    limit = cell.check.get("logit_gap_limit")
    correct = (limit is not None and cmp_["logit_gap_max"] is not None
               and cmp_["logit_gap_max"] <= float(limit))

    e2e = w["e2e"]
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result: Dict = {"correct": bool(correct),
                    "attempted": e2e["attempted"], "failed": e2e["failed"]}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if not trace:
        values = {"setup_s": setup_s,
                  "tokens_per_s": e2e["tokens_per_s"],
                  "ttft_p95_ms": e2e["ttft_p95_ms"],
                  "itl_p95_ms": e2e["itl_p95_ms"]}
        wanted = [m["name"] for m in cell.end_to_end]
    else:
        ctx = trace_context(cell, d0, w, spans, plan, trace_dir)
        device["busy_s"] = ctx["reduced"]["busy_s"]
        device["window_s"] = ctx["reduced"]["window_s"]
        values = {m["name"]: registry.metric_reader(m["name"])(ctx)
                  for m in cell.per_layer}
        wanted = [m["name"] for m in cell.per_layer]
        result["breakdown"] = {
            "device_ops": trace_reduce.top(ctx["reduced"]["op_seconds"]),
            "idle_gaps": trace_reduce.top(ctx["reduced"]["idle_by_span"])}
    result["metrics"] = {k: {"value": values[k], "unit": units[k]}
                         for k in wanted if values.get(k) is not None}
    result["device"] = device
    result["check"] = {"logit_gap_max": {
        "value": cmp_["logit_gap_max"], "limit": limit}}
    say(f"setup_s {setup_s:.3f}; window {e2e['attempted']} requests, "
        f"{e2e['n_itl']} token gaps; end to end {e2e}; metrics {values}")
    return result


def trace_context(cell, d0, w: Dict, spans, plan, trace_dir) -> Dict:
    """Everything a per-layer reader may read."""
    tr = trace_reduce.load(trace_reduce.find_trace(trace_dir))
    chip_ids = sorted(tr.chips)[:cell.chips]
    reduced = (trace_reduce.reduce(tr, chip_ids) if chip_ids
               else {"busy_s": 0.0, "window_s": 0.0, "op_seconds": {},
                     "idle_by_span": {}, "window": (0.0, 0.0)})
    return {
        "config": cell.config, "plan": plan,
        "peaks": peaks_for(d0.device_kind) if d0.platform == "tpu" else None,
        "flops": flops, "trace_reduce": trace_reduce,
        "records": w["records"], "spans": spans,
        "window": (w["t_open"], w["t_close"]),
        "trace_window": (w["marks"]["lo"], w["marks"]["hi"]),
        "chip": tr.chips.get(chip_ids[0]) if chip_ids else None,
        "reduced": reduced, "occupancy": w["occupancy"],
    }
