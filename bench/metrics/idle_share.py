"""Device: share of the traced window in which the chip ran no
operation, in %: 1 - (union of the device's busy intervals) / window.
"""


def read(ctx):
    red = ctx["reduced"]
    if not ctx["chip"] or red["window_s"] <= 0 or red["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
