"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time,
device time per operation and per program, and idle gaps attributed to
what the host was doing.

Device planes are the ``/device:TPU:<n>`` planes (each chip's own, its
SparseCore planes left out); their ``XLA Ops`` line holds one event per
operation the chip ran, named by the operation's HLO text
(``%fusion.12 = bf16[...] fusion(...)``), their ``XLA Modules`` line one
event per program execution (``jit__lambda(<fingerprint>)``).  Host
spans are the events, on any host plane line, whose name starts with
``bench.`` (``bench/spans.py``).  All times are on the profiler's one
clock, in nanoseconds.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class Chip:
    ops: List[Event]
    modules: List[Event]


@dataclasses.dataclass
class Trace:
    chips: Dict[int, Chip]
    spans: List[Event]


def _events(line) -> List[Event]:
    return [Event(e.name, float(e.start_ns), float(e.end_ns))
            for e in line.events]


def load(path) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    chips: Dict[int, Chip] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            chips[int(m.group(1))] = Chip(
                _events(lines["XLA Ops"]) if "XLA Ops" in lines else [],
                _events(lines["XLA Modules"])
                if "XLA Modules" in lines else [])
        elif plane.name.startswith("/host"):
            for ln in plane.lines:
                spans += [e for e in _events(ln)
                          if e.name.startswith("bench.")]
    return Trace(chips, spans)


def find_trace(root) -> Path:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    found = sorted(Path(root).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return found[-1]


# ----------------------------------------------------------------------
def union(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Merged intervals, clipped to ``[lo, hi]``."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of ``[lo, hi]`` that ``busy`` (merged) leaves idle."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


WINDOW = "bench.trace_window"


def window(trace: Trace, name: str = WINDOW) -> Interval:
    """The traced window: the host span ``name`` if present, else the
    extent of the device's operations."""
    marks = [s for s in trace.spans if s.name == name]
    if marks:
        return marks[0].start, marks[0].end
    ev = [e for c in trace.chips.values() for e in c.ops]
    return min(e.start for e in ev), max(e.end for e in ev)


def clipped(ev: Event, lo: float, hi: float) -> float:
    return max(0.0, min(ev.end, hi) - max(ev.start, lo))


def busy_seconds(chip: Chip, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(
        [(o.start, o.end) for o in chip.ops], lo, hi)) / 1e9


_KIND = re.compile(r"^%?([^\s=(]+?)(?:\.\d+)?(?=[\s=(]|$)")


def op_kind(name: str) -> str:
    """An operation's HLO name without its instance number:
    ``%paged_gqa_decode_attention.37 = f32[...] custom-call(...)`` is
    ``paged_gqa_decode_attention``."""
    m = _KIND.match(name)
    return m.group(1) if m else name


def op_seconds(chip: Chip, lo: float, hi: float) -> Dict[str, float]:
    """Device seconds per kind of operation inside the window."""
    out: Dict[str, float] = collections.defaultdict(float)
    for o in chip.ops:
        out[op_kind(o.name)] += clipped(o, lo, hi) / 1e9
    return dict(out)


def matching(events: Sequence[Event], pattern: str) -> List[Event]:
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e.name)]


def modules_holding(chip: Chip, pattern: str, lo: float, hi: float
                    ) -> List[Event]:
    """Program executions inside the window that ran at least one
    operation whose name matches ``pattern``."""
    hits = sorted(o.start for o in matching(chip.ops, pattern))
    out = []
    for m in chip.modules:
        if m.start < lo or m.end > hi:
            continue
        i = bisect.bisect_left(hits, m.start)
        if i < len(hits) and hits[i] <= m.end:
            out.append(m)
    return out


def innermost(spans: Sequence[Event], t: float) -> Optional[Event]:
    covering = [s for s in spans if s.start <= t <= s.end]
    return min(covering, key=lambda s: s.end - s.start) if covering else None


def idle_by_span(chip: Chip, spans: Sequence[Event], lo: float, hi: float
                 ) -> Dict[str, float]:
    """Idle seconds inside the window, by the innermost host span at
    each gap's midpoint (``"no host span"`` where none covers it)."""
    busy = union([(o.start, o.end) for o in chip.ops], lo, hi)
    out: Dict[str, float] = collections.defaultdict(float)
    for s, e in gaps(busy, lo, hi):
        sp = innermost(spans, (s + e) / 2)
        out[sp.name if sp else "no host span"] += (e - s) / 1e9
    return dict(out)


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def reduce(trace: Trace, chips: Sequence[int]) -> Dict[str, object]:
    """What the result line and the per-layer readers take: busy and
    window seconds (busy averaged over ``chips``), device seconds by
    operation, and idle seconds by host span (chip ``chips[0]``)."""
    lo, hi = window(trace)
    busy = [busy_seconds(trace.chips[c], lo, hi) for c in chips]
    first = trace.chips[chips[0]]
    return {
        "window": (lo, hi),
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy),
        "op_seconds": op_seconds(first, lo, hi),
        "idle_by_span": idle_by_span(
            first, [s for s in trace.spans if s.name != WINDOW], lo, hi),
    }
