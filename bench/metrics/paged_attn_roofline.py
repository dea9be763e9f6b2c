"""Kernels: the paged decode attention kernel's share of its roofline,
in %.

Least time at the chip's peaks for the work the decode steps of the
traced window needed, over the kernel's device time in that window.
Work per step is the live context of each lane (``kv_len`` rows of K
and V over every layer, plus q and out: ``bench/flops.py``), averaged
over the decode calls dispatched in the traced window and multiplied
by the decode programs the device ran there.  ``KERNEL`` is the
kernel's operation name as the program emits it today.
"""

import numpy as np

from bench.spans import DECODE

KERNEL = r"^%paged_gqa_decode_attention\.\d+ = .*tpu_custom_call"


def read(ctx):
    chip, peaks = ctx["chip"], ctx["peaks"]
    if not chip or peaks is None:
        return None
    tr, fl = ctx["trace_reduce"], ctx["flops"]
    lo, hi = ctx["reduced"]["window"]
    mods = tr.modules_holding(chip, KERNEL, lo, hi)
    kern = sum(o.end - o.start for o in tr.matching(chip.ops, KERNEL)
               if lo <= o.start and o.end <= hi
               and any(m.start <= o.start <= m.end for m in mods))
    a, b = ctx["trace_window"]
    calls = ctx["spans"].between(DECODE, a, b)
    if not mods or not calls or kern <= 0:
        return None
    work = [fl.paged_attn_work(ctx["config"], s.info["kv_lens"])
            for s in calls]
    least = fl.roofline_seconds(np.mean([w["flops"] for w in work]),
                                np.mean([w["bytes"] for w in work]),
                                peaks)["seconds"] * len(mods)
    return 100.0 * least / (kern / 1e9)
