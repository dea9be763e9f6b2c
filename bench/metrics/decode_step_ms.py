"""Model step: device time of one batched decode program, in ms.

Mean device duration of the program executions in the traced window
that ran the paged decode kernel (the decode step is the only program
that does).  ``KERNEL`` is the kernel's operation name as the program
emits it today.
"""

import numpy as np

KERNEL = r"^%paged_gqa_decode_attention\.\d+ = .*tpu_custom_call"


def read(ctx):
    chip = ctx["chip"]
    if not chip:
        return None
    lo, hi = ctx["reduced"]["window"]
    mods = ctx["trace_reduce"].modules_holding(chip, KERNEL, lo, hi)
    if not mods:
        return None
    return float(np.mean([m.end - m.start for m in mods])) / 1e6
