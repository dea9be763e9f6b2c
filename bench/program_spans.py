"""The host spans the serving program opens around its own work, with
their stats, from a run's profiler trace.

The engine names them in ``repro.obs.trace.STEP_SPANS`` (``serving.step``
and its children, the async stepper's ``serving.park``,
``serving.admit`` and ``serving.publish``) and opens each with
``jax.profiler.TraceAnnotation(name, **stats)``, so they sit on the
profiler's one clock beside the device's operations, in nanoseconds,
as ``bench/trace_reduce.py``'s events do.  A program that opens no such
span gives an empty list, and the readers built on this read nothing.
"""

from __future__ import annotations

import dataclasses
import functools
from pathlib import Path
from typing import Dict, List, Sequence

from bench import registry, trace_reduce

PREFIX = "serving."
STEP = "serving.step"
ADMIT = "serving.admit"
PUBLISH = "serving.publish"
#: the directory ``bench/harness.py`` writes a traced run's trace to
TRACE_DIR = registry.REPO / ".bench_trace"


@dataclasses.dataclass
class Span(trace_reduce.Event):
    stats: Dict[str, object] = dataclasses.field(default_factory=dict)


def load(path) -> List[Span]:
    """Every ``serving.*`` event on the host planes of one
    ``.xplane.pb``, in start order."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out: List[Span] = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name.startswith(PREFIX):
                    out.append(Span(e.name, float(e.start_ns),
                                    float(e.end_ns), dict(e.stats)))
    return sorted(out, key=lambda s: s.start)


@functools.lru_cache(maxsize=4)
def _cached(path: str, mtime_ns: int) -> List[Span]:
    return load(path)


def find(root=None) -> List[Span]:
    """The program spans of the newest trace under ``root`` (default
    ``TRACE_DIR``); empty when there is no trace or the program opened
    no span."""
    try:
        path = trace_reduce.find_trace(root or TRACE_DIR)
    except FileNotFoundError:
        return []
    return _cached(str(path), Path(path).stat().st_mtime_ns)


def inside(spans: Sequence[Span], name: str, lo: float, hi: float
           ) -> List[Span]:
    """Spans called ``name`` that lie wholly in ``[lo, hi]``."""
    return [s for s in spans
            if s.name == name and lo <= s.start and s.end <= hi]
