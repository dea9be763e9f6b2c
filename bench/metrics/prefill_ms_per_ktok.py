"""Model step: device time of prefill programs per 1,000 real prompt
tokens, in ms (padding excluded).

Device time: program executions in the traced window whose name is
``PROGRAM`` (the serving step programs as the program names them today)
and that run no paged decode kernel.  Tokens: the real tokens of the
prefill chunks dispatched inside the traced window (host spans).
"""

import re

from bench.spans import PREFILL

KERNEL = r"^%paged_gqa_decode_attention\.\d+ = .*tpu_custom_call"
PROGRAM = r"jit__lambda"


def read(ctx):
    chip = ctx["chip"]
    if not chip:
        return None
    tr = ctx["trace_reduce"]
    lo, hi = ctx["reduced"]["window"]
    dec = {id(m) for m in tr.modules_holding(chip, KERNEL, lo, hi)}
    rx = re.compile(PROGRAM)
    dev = sum(m.end - m.start for m in chip.modules
              if lo <= m.start and m.end <= hi and id(m) not in dec
              and rx.search(m.name))
    a, b = ctx["trace_window"]
    toks = sum(s.info["n"] for s in ctx["spans"].between(PREFILL, a, b))
    if not toks or dev <= 0:
        return None
    return dev / 1e6 / toks * 1000.0
