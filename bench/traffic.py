"""The one traffic generator: turns a mix's data file into requests.

A mix (``bench/traffic/<name>.json``) gives lengths as clipped
lognormals (``median``, ``sigma``, ``min``, ``max``) and either an
open loop (``"loop": "open"``, Poisson arrivals at ``rate_per_s``) or a
closed loop (``"loop": "closed"``, ``clients`` that each send their next
request when the previous one completes, cycling through a ``pool`` of
sizes with fresh token ids each time).

Every seed gets the same SET of sizes and arrival gaps — the quantiles
``(i + 0.5) / n`` of each distribution — in its own order, with its own
token ids.  So two seeds do the same work and differ only in how it
interleaves: run-to-run spread measures the system, not the draw.
Token ids are uniform over the vocabulary; answers are greedy with no
end-of-sequence token, so an answer is exactly ``max_new`` tokens long.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, List, Mapping, Tuple

import numpy as np

from bench.weights import seed32


@dataclasses.dataclass
class Req:
    idx: int
    prompt: np.ndarray
    max_new: int
    due: float = 0.0            # open loop: seconds after the window opens


@dataclasses.dataclass
class Plan:
    loop: str
    #: (prompt length, answer length) of request ``k % len(sizes)``
    sizes: List[Tuple[int, int]]
    seed: int
    vocab: int
    #: open loop: seconds after the window opens that request k is due
    due: List[float] = dataclasses.field(default_factory=list)
    clients: int = 0
    #: closed loop: the first request of client ``i`` is cut to
    #: ``first_new[i]`` tokens so the lanes finish out of phase
    first_new: List[int] = dataclasses.field(default_factory=list)

    def request(self, k: int) -> Req:
        """Request ``k``: its size from the cycle, token ids of its own."""
        p, o = self.sizes[k % len(self.sizes)]
        rng = np.random.default_rng([seed32(self.seed, "prompt"), k])
        return Req(k, rng.integers(0, self.vocab, p, np.int32), o,
                   self.due[k] if self.due else 0.0)

    @property
    def prompt_lengths(self) -> List[int]:
        """Every distinct prompt length this plan can send."""
        return sorted({p for p, _ in self.sizes})


def quantile_lengths(spec: Mapping, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles ``(i + 0.5) / n`` of a lognormal
    with the given median and sigma, clipped to ``[min, max]``."""
    nd = statistics.NormalDist()
    med, sig = float(spec["median"]), float(spec["sigma"])
    out = [med * math.exp(sig * nd.inv_cdf((i + 0.5) / n)) for i in range(n)]
    return np.clip(np.rint(out), int(spec["min"]), int(spec["max"])
                   ).astype(np.int64)


def quantile_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` exponential inter-arrival gaps at the quantiles
    ``(i + 0.5) / n`` (mean 1 / rate)."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def build(mix: Mapping, seed: int, seconds: float, vocab: int) -> Plan:
    rng = np.random.default_rng(seed32(seed, "traffic"))
    if mix["loop"] == "open":
        n = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
    elif mix["loop"] == "closed":
        n = int(mix["pool"])
    else:
        raise ValueError(f"loop {mix['loop']!r}: 'open' or 'closed'")
    plens = rng.permutation(quantile_lengths(mix["prompt"], n))
    olens = rng.permutation(quantile_lengths(mix["output"], n))
    plan = Plan(mix["loop"], [(int(p), int(o)) for p, o in zip(plens, olens)],
                seed, vocab)
    if mix["loop"] == "open":
        plan.due = np.cumsum(rng.permutation(
            quantile_gaps(float(mix["rate_per_s"]), n))).tolist()
        return plan
    c = plan.clients = int(mix["clients"])
    frac = float(mix.get("ramp", {}).get("first_output_max_frac", 1.0))
    # client i's first answer is cut to an even share of up to frac of
    # its length, so completions (and the next prefills) spread out
    plan.first_new = [max(1, int(round(plan.sizes[i % n][1] * frac
                                       * (i + 1) / c))) for i in range(c)]
    return plan


def warmup_prompts(lengths: List[int], seed: int, vocab: int
                   ) -> List[np.ndarray]:
    """One prompt of each length, with token ids of their own (they
    share no prefix with a plan's prompts)."""
    rng = np.random.default_rng(seed32(seed, "warmup"))
    return [rng.integers(0, vocab, n, np.int32) for n in lengths]


def summary(plan: Plan) -> Dict[str, float]:
    p = [a for a, _ in plan.sizes]
    o = [b for _, b in plan.sizes]
    return {"sizes": len(p), "prompt_median": float(np.median(p)),
            "prompt_max": max(p), "output_median": float(np.median(o)),
            "output_max": max(o)}
