"""Tiny cells for exercising the benchmark harness on the CPU."""

import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import registry  # noqa: E402

TINY = {"hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
        "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
        "tie_word_embeddings": True, "torch_dtype": "bfloat16",
        "qk_norm": True, "reference": "dense"}

LENGTHS = {"prompt": {"median": 40, "sigma": 0.8, "min": 8, "max": 100},
           "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 16}}
MIXES = {
    "open": dict(loop="open", rate_per_s=6.0, **LENGTHS),
    "closed": dict(loop="closed", clients=4, pool=16,
                   ramp={"first_output_max_frac": 0.5}, **LENGTHS),
}


def cell(loop: str = "open", *, limit: float = 0.05, **conf) -> registry.Cell:
    """A cell of the tiny model under a small open or closed loop, with
    the metric lists ``qwen3-1.7b.chat`` has in ``BENCHMARK.json``."""
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    name = spec["workloads"][0]["name"]
    e2e = registry.metrics_for(spec["end_to_end"], name, [])
    return registry.Cell(
        name=name, config_name="tiny", config=dict(TINY, **conf),
        traffic_name=loop, traffic=MIXES[loop],
        engine={"max_running": 4, "max_len": 128, "page_size": 16,
                "prefill_chunk": 32},
        check={"tokens": 32, "logit_gap_limit": limit}, chips=1,
        end_to_end=e2e,
        per_layer=registry.metrics_for(spec["per_layer"], name,
                                        [m["name"] for m in e2e]))
