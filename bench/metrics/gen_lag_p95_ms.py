"""Load generator: how late the open loop sent its requests.

95th percentile, in ms, of send time minus due time over the requests
due in the window (host clock).  A starved generator would read as a
fast server; this says how far to trust the TTFT tail.  Closed loops
have no due time: nothing to read.
"""

import numpy as np


def read(ctx):
    if ctx["plan"].loop != "open":
        return None
    t0, t1 = ctx["window"]
    lags = [r.sent - r.due for r in ctx["records"] if t0 <= r.due < t1]
    if not lags:
        return None
    return float(np.percentile(lags, 95)) * 1e3
