"""The benchmark harness on the CPU: traffic, finding pieces by name,
refusing a host without a chip, and the result line's keys."""

import hashlib
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import bench_tiny
from bench_tiny import REPO

from bench import harness, registry, traffic

CONTRACT_KEYS = ["correct", "attempted", "failed", "breakdown", "metrics",
                 "device", "check"]


@pytest.mark.parametrize("mix", ["chat", "decode-batch", "rag"])
def test_traffic_is_the_seeds_and_keeps_its_ranges(mix):
    m = json.loads((REPO / "bench" / "traffic" / f"{mix}.json").read_text())
    a = traffic.build(m, 2 ** 33 + 5, 40.0, 1000)
    b = traffic.build(m, 2 ** 33 + 5, 40.0, 1000)
    c = traffic.build(m, 11, 40.0, 1000)
    assert a.sizes == b.sizes and a.due == b.due
    last = len(a.sizes) + 2 if a.loop == "closed" else len(a.sizes) - 1
    for k in (0, 3, last):
        assert (a.request(k).prompt == b.request(k).prompt).all()
    # another seed: the same set of sizes and gaps, in another order
    for i in (0, 1):
        assert sorted(x[i] for x in a.sizes) == sorted(x[i] for x in c.sizes)
    assert a.sizes != c.sizes
    if a.due:
        assert sorted(a.due) != sorted(c.due) or a.due != c.due
        gaps = sorted(x - y for x, y in zip(a.due, [0.0] + a.due[:-1]))
        gaps_c = sorted(x - y for x, y in zip(c.due, [0.0] + c.due[:-1]))
        assert gaps == pytest.approx(gaps_c)
        assert a.due[-1] <= 40.0
    for p, o in a.sizes:
        assert m["prompt"]["min"] <= p <= m["prompt"]["max"]
        assert m["output"]["min"] <= o <= m["output"]["max"]
    r = a.request(1)
    assert len(r.prompt) == a.sizes[1][0] and r.max_new == a.sizes[1][1]
    assert 0 <= r.prompt.min() and r.prompt.max() < 1000


def _digest(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_additions_are_found_by_name_without_edits(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    before = _digest(tmp_path / "bench")
    b = tmp_path / "bench"
    (b / "configs" / "new-model.json").write_text(json.dumps(
        dict(bench_tiny.TINY)))
    (b / "traffic" / "new-mix.json").write_text(json.dumps(
        bench_tiny.MIXES["closed"]))
    (b / "cells" / "new-model.new-mix.json").write_text(json.dumps(
        {"engine": {"max_running": 2, "max_len": 64, "page_size": 16},
         "check": {"tokens": 8, "logit_gap_limit": 0.1}}))
    (b / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    spec["configs"].append({"name": "new-model", "source": "x",
                            "file": "bench/configs/new-model.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "new-model.new-mix",
                              "config": "new-model", "traffic": "new-mix",
                              "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "new_metric", "unit": "%",
                              "better": "higher", "source": "host_clock",
                              "layer": "device", "moves": "tokens_per_s",
                              "workloads": ["new-model.new-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = registry.cell("new-model.new-mix", repo=tmp_path, bench_dir=b)
    assert cell.config["hidden_size"] == 64
    assert cell.traffic == bench_tiny.MIXES["closed"]
    assert cell.engine["max_len"] == 64
    assert "new_metric" in [m["name"] for m in cell.per_layer]
    assert registry.metric_reader("new_metric", b)({}) == 42.0
    after = _digest(b)
    assert {k: v for k, v in after.items() if k in before} == before
    # an existing cell still sees only its own metrics
    old = registry.cell(spec["workloads"][0]["name"], repo=tmp_path,
                        bench_dir=b)
    assert "new_metric" not in [m["name"] for m in old.per_layer]


def test_run_refuses_a_host_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(REPO / "bench" / "run.py"), "--workload",
         "qwen3-1.7b.chat", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "needs 1 TPU chip" in p.stderr


@pytest.mark.parametrize("trace", [False, True])
def test_result_line_has_only_the_contract_keys(trace):
    cell = bench_tiny.cell("open" if not trace else "closed")
    res = harness.run(cell, seed=2 ** 32 + 9, seconds=2.0, trace=trace,
                      t_start=time.perf_counter())
    assert list(res) == [k for k in CONTRACT_KEYS
                         if k != "breakdown" or trace]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"}
    names = {m["name"] for m in (cell.per_layer if trace
                                 else cell.end_to_end)}
    assert set(res["metrics"]) <= names
    if not trace:
        assert set(res["metrics"]) == names
    else:
        assert {"busy_s", "window_s"} <= set(res["device"])
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    chk = res["check"]["logit_gap_max"]
    assert chk["value"] <= chk["limit"]
    json.dumps(res)
