"""Async front end + scheduler: how long a request waited for its first
slot, p95 in ms.

Over the requests due in the window, ``t_scheduled - t_submit`` of each
request's handle: both are the engine's own stamps (its clock is
``time.perf_counter``), from the client's ``submit`` to the first slot
grant.  A request never granted a slot counts with its wait so far, up
to the moment this reads (after the window and the engine's shutdown),
and is never dropped.  A program without the stamps reads nothing.
"""

import time

import numpy as np


def read(ctx):
    t0, t1 = ctx["window"]
    now = time.perf_counter()
    waits = []
    for r in ctx["records"]:
        if not t0 <= r.due < t1:
            continue
        t_submit = getattr(r.handle, "t_submit", None)
        if t_submit is None:
            return None
        t_sched = r.handle.t_scheduled
        waits.append((now if t_sched is None else t_sched) - t_submit)
    if not waits:
        return None
    return float(np.percentile(waits, 95)) * 1e3
