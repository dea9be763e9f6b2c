"""Model step: the decode program's share of its HBM roofline, in %.

Least time of one decode step: the bytes it must read at the chip's HBM
bandwidth — every parameter of the model once (``bench/flops.py``'s
``param_counts``, in bf16) plus the KV pages the paged kernel reads,
``kv_pages x page_size x kv_bytes_per_token`` — with ``kv_pages`` the
mean of the program's ``serving.decode`` span stat (Σ over the step's
lanes of ``cdiv(pos + 1, page_size)``) over the traced window.  Divided
by the mean device time of the window's decode programs: the program
executions that ran the paged kernel, as ``decode_step_ms`` counts
them.  ``page_size`` is the cell's engine setting, found by the
configuration and traffic the run served.  A program without the span
or its stat reads nothing.
"""

import numpy as np

from bench import flops, program_spans, registry, traffic

KERNEL = r"^%paged_gqa_decode_attention\.\d+ = .*tpu_custom_call"
DECODE = "serving.decode"


def step_bytes(conf, kv_pages: float, page_size: int) -> float:
    """Bytes one decode step reads at the least: the bf16 parameters and
    ``kv_pages`` pages of K and V over every layer."""
    return (flops.param_counts(conf)["total"] * flops.BF16_BYTES
            + kv_pages * page_size * flops.kv_bytes_per_token(conf))


def page_size(conf, plan):
    """The page size of the cells whose configuration is ``conf`` and
    whose traffic gives ``plan``'s prompt and answer lengths (a set that
    does not depend on the seed); None unless exactly one setting
    fits."""
    spec = registry.load_json(registry.REPO / "BENCHMARK.json")
    files = {c["name"]: c["file"] for c in spec["configs"]}
    n = len(plan.sizes)
    lengths = [sorted(x) for x in zip(*plan.sizes)]
    found = set()
    for w in spec["workloads"]:
        mix = registry.load_json(registry.BENCH_DIR / "traffic"
                                 / f"{w['traffic']}.json")
        if (registry.load_json(registry.REPO / files[w["config"]]) != conf
                or mix["loop"] != plan.loop
                or lengths != [sorted(traffic.quantile_lengths(
                    mix[k], n).tolist()) for k in ("prompt", "output")]):
            continue
        cell = registry.load_json(registry.BENCH_DIR / "cells"
                                  / f"{w['name']}.json")
        found.add(int(cell["engine"]["page_size"]))
    return found.pop() if len(found) == 1 else None


def read(ctx):
    chip, peaks = ctx["chip"], ctx["peaks"]
    if not chip or peaks is None:
        return None
    lo, hi = ctx["reduced"]["window"]
    mods = ctx["trace_reduce"].modules_holding(chip, KERNEL, lo, hi)
    pages = [s.stats["kv_pages"]
             for s in program_spans.inside(program_spans.find(), DECODE,
                                           lo, hi)
             if "kv_pages" in s.stats]
    ps = page_size(ctx["config"], ctx["plan"])
    if not mods or not pages or ps is None:
        return None
    least = (step_bytes(ctx["config"], float(np.mean(pages)), ps)
             / float(peaks["hbm_bytes_per_s"]))
    step = float(np.mean([m.end - m.start for m in mods])) / 1e9
    return 100.0 * least / step
