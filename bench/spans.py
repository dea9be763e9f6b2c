"""Host spans around the calls into each layer of the engine instance a
run built, for the traced run only.

Each wrapped call opens a ``jax.profiler.TraceAnnotation`` (so it lands
on the profiler's clock beside the device's operations) and appends a
``perf_counter`` record with what the call was given: the chunk of a
prefill, the live context of each decode lane.  The program's source is
not touched; only attributes of the instance are replaced.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List

import numpy as np

#: span names as they appear in the trace
STEP = "bench.core.step"
SCHEDULE = "bench.scheduler.step"
PREFILL = "bench.runner.prefill_chunk"
DECODE = "bench.runner.decode"
SAMPLE = "bench.sample"


@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: float
    info: Dict[str, Any]


class Spans:
    def __init__(self) -> None:
        self.records: List[Span] = []

    def between(self, name: str, t0: float, t1: float) -> List[Span]:
        return [s for s in self.records
                if s.name == name and t0 <= s.t0 and s.t1 <= t1]


def _wrap(spans: Spans, name: str, fn, info=None):
    import jax

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            out = fn(*args, **kwargs)
        spans.records.append(Span(name, t0, time.perf_counter(),
                                  info(args, kwargs, out) if info else {}))
        return out
    return wrapped


def _prefill_info(args, kwargs, out):
    return {"start": int(kwargs["start"]), "n": len(args[0])}


def _decode_info(args, kwargs, out):
    pos = np.asarray(args[1])
    return {"kv_lens": (pos[pos >= 0] + 1).tolist()}


def _step_info(args, kwargs, out):
    return {"work": not out.idle}


def instrument(engine, spans: Spans) -> None:
    """Wrap the stepper's calls into scheduler, runner and sampler."""
    from repro.serving import core as core_mod
    core = engine.core
    core.step = _wrap(spans, STEP, core.step, _step_info)
    core.scheduler.step = _wrap(spans, SCHEDULE, core.scheduler.step)
    core.runner.prefill_chunk = _wrap(spans, PREFILL,
                                      core.runner.prefill_chunk,
                                      _prefill_info)
    core.runner.decode = _wrap(spans, DECODE, core.runner.decode,
                               _decode_info)
    for fn in ("sample", "sample_grouped"):
        setattr(core_mod, fn, _wrap(spans, SAMPLE, getattr(core_mod, fn)))
