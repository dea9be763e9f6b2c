"""``decode_hbm_roofline``'s byte count and its lookup of the cell's page
size, against hand reckoning."""

import json

from bench_tiny import REPO

from bench import registry, traffic

#: the reader's module namespace, loaded by name as the harness loads it
reader = registry.metric_reader("decode_hbm_roofline").__globals__


def conf(name):
    return json.loads((REPO / "bench" / "configs" / f"{name}.json")
                      .read_text())


def test_granite_stage_step_bytes_at_fixed_pages():
    c = conf("granite-3-8b-pp2")
    # 4,186,095,616 bf16 parameters, and 1,200 pages of 16 tokens at
    # 20 layers x K and V x 8 kv heads x 128 x 2 B = 81,920 B a token
    want = 4_186_095_616 * 2 + 1_200 * 16 * 81_920
    assert reader["step_bytes"](c, 1_200, 16) == want
    # at 819 GB/s: 10.2 ms of weights and 1.9 ms of pages
    assert round(want / 819e9 * 1e3, 1) == 12.1


def test_qwen3_step_bytes_at_fixed_pages():
    c = conf("qwen3-1.7b")
    want = 1_720_574_976 * 2 + 388 * 16 * 114_688
    assert reader["step_bytes"](c, 388, 16) == want


def test_page_size_found_by_configuration_and_traffic():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = registry.cell(w["name"])
        plan = traffic.build(cell.traffic, 2 ** 31 + 7, 50.0,
                             int(cell.config["vocab_size"]))
        assert reader["page_size"](cell.config, plan) == \
            cell.engine["page_size"], w["name"]
    # a configuration no cell runs finds nothing
    cell = registry.cell(spec["workloads"][0]["name"])
    plan = traffic.build(cell.traffic, 1, 50.0, 256)
    assert reader["page_size"](dict(cell.config, hidden_size=1),
                               plan) is None
