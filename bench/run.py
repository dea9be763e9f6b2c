#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration with weights from ``--seed``, warms up
every shape its traffic uses, measures ``--seconds`` of traffic through
the serving engine, checks what was served against the plain
reference, and prints one JSON object as the last line of standard
output: the end-to-end metrics with ``--trace 0``, the per-layer
metrics (from a profiler trace of a few seconds of the window) with
``--trace 1``.  The numbers compared by the check are the last lines of
standard error and the last key of the result.

Refuses to run (exit 2, no result) without an accelerator holding the
chips the cell asks for.  JAX's persistent compilation cache is kept in
``$JAX_COMPILATION_CACHE_DIR`` when set, else in ``.jax_cache`` at the
root of the checkout.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]
# the TPU runtime otherwise writes its logs to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_compile_cache(repo: Path = REPO) -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        repo / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    args = parse(argv)
    from bench import registry
    cell = registry.cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX finds {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    use_compile_cache()
    from bench import harness
    result = harness.run(cell, seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace), t_start=T_START,
                         devices=devices[:cell.chips])
    for name, c in result["check"].items():
        print(f"check: {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
