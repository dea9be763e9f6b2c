"""KV pool: share of the pool's pages in use, in %.

Mean over the ``serving.step`` spans of the traced window of their
stats ``pages_used / pages_reserved``: pages some sequence holds after
the step's scheduling, over the pages the pool reserves (all but the
scratch page).
"""

import numpy as np

from bench import program_spans


def read(ctx):
    lo, hi = ctx["reduced"]["window"]
    shares = [s.stats["pages_used"] / s.stats["pages_reserved"]
              for s in program_spans.inside(program_spans.find(),
                                            program_spans.STEP, lo, hi)
              if s.stats.get("pages_reserved")]
    if not shares:
        return None
    return 100.0 * float(np.mean(shares))
