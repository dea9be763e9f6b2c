#!/usr/bin/env python3
"""Find the highest open-loop rate a cell sustains: one engine, one
window per rate, in one process.

    python3 bench/sweep.py --workload qwen3-1.7b.chat --seed 7 \
        --seconds 20 --rates 2,3,4,5

For each rate it prints the requests due in the window, the tokens
delivered per second, TTFT p50/p95 of the first and second half of the
window, and how many requests had no first token when it closed.  A
rate is sustained when the second half's TTFT does not run away from
the first's and the backlog at the close stays near zero; the cell's
mix takes about four fifths of the highest such rate.  Never part of a
run of the benchmark.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    from bench import registry
    from bench.run import use_compile_cache
    cell = registry.cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    use_compile_cache()
    sweep(cell, args.seed, args.seconds,
          [float(r) for r in args.rates.split(",")])
    return 0


def sweep(cell, seed, seconds, rates):
    import numpy as np

    from bench import harness, traffic
    vocab = int(cell.config["vocab_size"])
    # a seed of its own per rate: no window's prompts are a prefix of
    # another's, so the prefix cache finds nothing and no new shape runs
    plans = [traffic.build(dict(cell.traffic, rate_per_s=r), seed + i,
                           seconds, vocab) for i, r in enumerate(rates)]
    sess = harness.Session(cell, seed=seed, plans=plans, trace=False)
    rows = []
    for rate, plan in zip(rates, plans):
        w = sess.measure(plan, seconds)
        t0, t1 = w["t_open"], w["t_close"]
        mid = (t0 + t1) / 2
        due = [r for r in w["records"] if t0 <= r.due < t1]
        halves = [[r.stamps[0] - r.due for r in due
                   if r.stamps and (r.due < mid) == first]
                  for first in (True, False)]
        late = sum(1 for r in due if not r.stamps or r.stamps[0] > t1)
        row = {"rate": rate, "due": len(due),
               "tokens_per_s": w["e2e"]["tokens_per_s"],
               "itl_p95_ms": w["e2e"]["itl_p95_ms"],
               "ttft_p50_ms": [float(np.median(h)) * 1e3 if h else None
                               for h in halves],
               "ttft_p95_ms": [float(np.percentile(h, 95)) * 1e3
                               if h else None for h in halves],
               "no_first_token_at_close": late,
               "compiles_in_window": w["n_compiles"]}
        rows.append(row)
        print("sweep: " + json.dumps(row), flush=True)
        sess.cancel(w["records"])
    sess.close()
    print(json.dumps(rows), flush=True)
    return rows


if __name__ == "__main__":
    sys.exit(main())
