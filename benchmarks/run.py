"""Benchmark harness (deliverable d) — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Sections:
  numa_sim       Table 1, Figs 10/11/9/12/13, headline claims
  engine_bench   ArcLight engine + serving frontend + Sync A/B
  serving_bench  bucket vs continuous-batching engines, Poisson arrivals
  kernels_bench  Q4_0 GEMM + decode attention kernels
  roofline_bench per-(arch x shape) dominant roofline terms
"""

from __future__ import annotations

import sys
import traceback


def main() -> int:
    """Run every section; a failed section is recorded as
    ``SECTION-FAILED`` and the others keep going, but the exit code
    is 1."""
    from . import (engine_bench, kernels_bench, numa_sim, roofline_bench,
                   serving_bench)
    print("name,us_per_call,derived")
    failed = []
    for mod in (numa_sim, engine_bench, serving_bench, kernels_bench,
                roofline_bench):
        try:
            for name, us, derived in mod.all_rows():
                print(f"{name},{us:.1f},{derived}")
        except Exception:  # noqa: BLE001 — keep other sections alive
            traceback.print_exc()
            print(f"{mod.__name__},0.0,SECTION-FAILED", file=sys.stderr)
            failed.append(mod.__name__)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
