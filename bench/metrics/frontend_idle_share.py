"""Async front end: share of the traced window in which the chip idled
while the stepper admitted requests or published tokens, in %.

Device idle time in the window whose innermost program span
(``bench/program_spans.py``) is ``serving.admit`` or ``serving.publish``
(``trace_reduce.idle_by_span``), over the window.
"""

from bench import program_spans


def read(ctx):
    chip = ctx["chip"]
    spans = program_spans.find()
    if not chip or not spans:
        return None
    lo, hi = ctx["reduced"]["window"]
    idle = ctx["trace_reduce"].idle_by_span(chip, spans, lo, hi)
    front = idle.get(program_spans.ADMIT, 0.0) + idle.get(
        program_spans.PUBLISH, 0.0)
    return 100.0 * front / ((hi - lo) / 1e9)
