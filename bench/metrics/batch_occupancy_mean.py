"""Scheduler: mean decode-batch occupancy over the window.

Lanes decoding per decode step, from the program's own
``serving.batch.occupancy`` histogram (sum over count), its change
between the window's opening and its close.
"""


def read(ctx):
    total, count = ctx["occupancy"]
    return total / count if count else None
