#!/usr/bin/env python3
"""Readings that a cell's check limit is set from, in one process.

    python3 bench/calibrate.py --workload qwen3-1.7b.chat --seconds 40 \
        --seeds 11,12,13

One engine is set up and warmed once; for every seed it serves that
seed's weights and traffic for one window at the cell's own load, then
runs the plain reference over the seed's sample of finished requests
(the run's own check) and the control — the reference computed in int8
— over the same prompts and served tokens.  Per seed it prints the
widest gap of the served tokens (the lower reading's material) and of
the control's tokens (the upper reading's).  Never part of a run of the
benchmark; the limits in ``bench/cells/<cell>.json`` are set by hand
from these readings.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    from bench import registry
    from bench.run import use_compile_cache
    cell = registry.cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    use_compile_cache()
    calibrate(cell, [int(s) for s in args.seeds.split(",")], args.seconds)
    return 0


def calibrate(cell, seeds, seconds):
    from bench import harness, traffic
    vocab = int(cell.config["vocab_size"])
    plans = [traffic.build(cell.traffic, s, seconds, vocab)
             for s in seeds]
    sess = harness.Session(cell, seed=seeds[0], plans=plans, trace=False)
    rows = []
    for seed, plan in zip(seeds, plans):
        sess.set_weights(seed)
        w = sess.measure(plan, seconds)
        sess.cancel(w["records"])
        c = harness.sample_and_compare(cell, seed, w["records"],
                                       params=sess.params, control=True)
        row = dict(seed=seed, **c)
        rows.append(row)
        print("calibrate: " + json.dumps(row), flush=True)
    sess.close()
    print(json.dumps(rows), flush=True)
    return rows


if __name__ == "__main__":
    sys.exit(main())
