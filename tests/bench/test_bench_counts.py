"""Counts of ``bench/flops.py`` against hand reckoning, and the peaks
table's refusal of an unknown chip."""

import json

import pytest

from bench_tiny import REPO

from bench import flops, peaks


def conf(name):
    return json.loads((REPO / "bench" / "configs" / f"{name}.json")
                      .read_text())


def test_qwen3_parameters_and_kv():
    c = conf("qwen3-1.7b")
    # per layer: q 2048x2048, k/v 2048x1024, o 2048x2048, SwiGLU
    # 3 x 2048x6144; gains 2 x 2048 + q/k norm 2 x 128
    layer = (2048 * 2048 * 2 + 2048 * 1024 * 2 + 3 * 2048 * 6144
             + 2 * 2048 + 2 * 128)
    total = 28 * layer + 151_936 * 2048 + 2048      # tied head
    p = flops.param_counts(c)
    assert p["total"] == total == 1_720_574_976
    assert p["head"] == 0
    assert flops.kv_bytes_per_token(c) == 28 * 2 * 8 * 128 * 2 == 114_688


def test_granite_stage_parameters_and_kv():
    c = conf("granite-3-8b-pp2")
    layer = (4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 12_800
             + 2 * 4096)
    total = 20 * layer + 49_155 * 4096 + 4096       # tied head
    assert flops.param_counts(c)["total"] == total == 4_186_095_616
    assert flops.kv_bytes_per_token(c) == 20 * 2 * 8 * 128 * 2 == 81_920
    # the published 40 layers give the published 8.17 B
    whole = dict(c, num_hidden_layers=40)
    assert round(flops.param_counts(whole)["total"] / 1e9, 2) == 8.17


def test_paged_attention_live_bytes():
    c = conf("qwen3-1.7b")
    lens = [100, 1, 700]
    w = flops.paged_attn_work(c, lens)
    kv = 801 * 114_688
    qo = 28 * 3 * 16 * 128 * (2 + 4)       # bf16 q in, f32 out
    assert w["bytes"] == kv + qo
    assert w["flops"] == 28 * 4 * 801 * 16 * 128


def test_token_and_chunk_flops_agree():
    c = conf("qwen3-1.7b")
    p = flops.param_counts(c)
    one = flops.token_flops(c, 10, logits=False)
    assert one == 2 * 28 * p["layer_matmul"] + 28 * 4 * 10 * 16 * 128
    # a chunk of n tokens is the sum of its tokens
    chunk = flops.prefill_chunk_flops(c, 5, 3, last=True)
    assert chunk == (sum(flops.token_flops(c, 5 + i + 1, logits=False)
                         for i in range(3)) + 2 * 151_936 * 2048)


def test_roofline_bound():
    pk = peaks.peaks_for("TPU v5 lite")
    r = flops.roofline_seconds(197e12, 819e9 * 2, pk)
    assert r == {"seconds": 2.0, "bound": "memory"}


def test_peaks_refuse_unknown_device():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
