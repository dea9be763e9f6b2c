"""The check that decides ``correct``, on the CPU at a tiny size.

* A run with the timed path broken underneath — every token altered
  where the engine produces it — reads ``correct`` false, skipping only
  the harness's look for a chip.
* The control (the reference computed in int8 in the program's place)
  reads far above a sound program on the same prompts and tokens.
"""

import time

import pytest

import bench_tiny

from bench import harness
from bench.calibrate import calibrate


@pytest.mark.parametrize("where", ["decode", "prefill"])
def test_altered_tokens_read_not_correct(monkeypatch, where):
    from repro.serving import core as core_mod
    vocab = bench_tiny.TINY["vocab_size"]
    if where == "decode":
        real = core_mod.sample_grouped
        monkeypatch.setattr(core_mod, "sample_grouped",
                            lambda *a: (real(*a) + 1) % vocab)
    else:
        real = core_mod.sample
        monkeypatch.setattr(core_mod, "sample",
                            lambda *a: (real(*a) + 1) % vocab)
    res = harness.run(bench_tiny.cell("open"), seed=77, seconds=2.0,
                      trace=False, t_start=time.perf_counter())
    chk = res["check"]["logit_gap_max"]
    assert res["correct"] is False
    assert chk["value"] > chk["limit"]


def test_control_reads_above_a_sound_program():
    # float32 weights: the sound program agrees with the float32
    # reference to rounding; the int8 control does not
    cell = bench_tiny.cell("open", torch_dtype="float32")
    rows = calibrate(cell, [5, 2 ** 31 + 3], 2.0)
    for r in rows:
        assert r["tokens"] > 0
        assert r["control_gap_max"] > 0
        assert r["control_gap_max"] > 10 * r["logit_gap_max"]
