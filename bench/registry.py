"""Finds a cell's pieces by the names in ``BENCHMARK.json``.

* a configuration: the file its ``configs`` entry names;
* a traffic mix: ``bench/traffic/<traffic>.json``;
* a cell's engine settings and check limits: ``bench/cells/<cell>.json``;
* a per-layer metric: the reader ``bench/metrics/<metric>.py``, whose
  ``read(ctx)`` returns the value or ``None`` when it finds nothing.

Adding any of these is adding a file and an entry; no file that is
there changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Mapping

BENCH_DIR = Path(__file__).resolve().parent
REPO = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    config: Dict
    traffic_name: str
    traffic: Dict
    engine: Dict
    check: Dict
    chips: int
    end_to_end: List[Dict]
    per_layer: List[Dict]


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def metrics_for(entries: List[Mapping], cell: str,
                e2e: List[str]) -> List[Dict]:
    """Metrics a cell reports: those listing it, or, without a
    ``workloads`` key, those whose end-to-end metric it reports."""
    out = []
    for m in entries:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(dict(m))
        elif "moves" not in m or m["moves"] in e2e:
            out.append(dict(m))
    return out


def cell(name: str, repo: Path = REPO, bench_dir: Path = BENCH_DIR) -> Cell:
    spec = load_json(repo / "BENCHMARK.json")
    try:
        w = next(w for w in spec["workloads"] if w["name"] == name)
    except StopIteration:
        known = [w["name"] for w in spec["workloads"]]
        raise KeyError(f"no workload {name!r}; known: {known}") from None
    c = next(c for c in spec["configs"] if c["name"] == w["config"])
    e2e = metrics_for(spec["end_to_end"], name, [])
    per_layer = metrics_for(spec["per_layer"], name,
                            [m["name"] for m in e2e])
    cell_file = load_json(bench_dir / "cells" / f"{name}.json")
    return Cell(name=name, config_name=c["name"],
                config=load_json(repo / c["file"]),
                traffic_name=w["traffic"],
                traffic=load_json(bench_dir / "traffic"
                                  / f"{w['traffic']}.json"),
                engine=cell_file["engine"], check=cell_file["check"],
                chips=int(w["chips"]), end_to_end=e2e, per_layer=per_layer)


def metric_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
