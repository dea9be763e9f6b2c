"""CPU rehearsal of ``chip_smoke.py``'s phases.

The smoke itself refuses any backend but a TPU; its phase functions
run here at ``reduced_config`` widths.  The paged decode attention is
steered onto the Pallas kernel in interpret mode (on the CPU the ops
wrapper would otherwise take the jnp reference), so the kernel the
chip runs is the one these greedy tokens come from.
"""

import functools
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.kernels import ops  # noqa: E402

_PAGED_ATTENTION = ops.paged_gqa_decode_attention

#: smoke shapes cut for the CPU: the same phases, chunking and prefix
#: sharing, at reduced widths and lengths
SMALL = dict(lengths=(12, 60), shared=24, chunk=32)
ENGINE = dict(page_size=8, prefill_chunk=32, max_len=96)
MAX_NEW = 8


@pytest.fixture(scope="module")
def reduced():
    from repro.serving.worker import build_serving_model
    return build_serving_model(chip_smoke.ARCH, seed=0)


def _route_paged_attention(monkeypatch, impl):
    """Steer the model's paged decode attention onto ``impl``."""
    monkeypatch.setattr(ops, "paged_gqa_decode_attention", functools.partial(
        _PAGED_ATTENTION, impl=impl))


def _serve(model, params, prompts, **kw):
    eng = chip_smoke.start_engine(model, params, n_slots=len(prompts),
                                  **ENGINE, **kw)
    try:
        return chip_smoke.serve(eng, prompts, max_new=MAX_NEW), eng
    finally:
        eng.shutdown()


def test_prompts_share_prefix_and_cover_both_prefill_paths():
    ps = chip_smoke.make_prompts(3, 512, **SMALL)
    assert len(ps) == chip_smoke.N_REQUESTS
    assert ps == chip_smoke.make_prompts(3, 512, **SMALL)
    assert ps[0][:24] == ps[1][:24]
    assert min(len(ps[0]), len(ps[1])) > 32      # chunked (resumed)
    assert len(ps[2]) <= 32                       # one-shot
    assert all(12 <= len(p) <= 60 for p in ps)
    full = chip_smoke.make_prompts(0, 151_936)
    assert all(64 <= len(p) <= 400 for p in full)
    assert full[0][:256] == full[1][:256]


def test_phases_kernel_parity_and_agreement(reduced, monkeypatch):
    """Greedy tokens served through the Pallas kernel (interpret mode)
    equal the jnp-reference engine's, and the plain forward agrees with
    them teacher-forced — at every position: the reduced model is
    float32, so the smoke's tie allowance (bf16 rounding) has nothing
    to forgive."""
    model, params = reduced
    prompts = chip_smoke.make_prompts(1, model.cfg.vocab_size, **SMALL)
    _route_paged_attention(monkeypatch, "kernel")
    outputs, eng = _serve(model, params, prompts)
    assert [len(o) for o in outputs] == [MAX_NEW] * len(prompts)
    # prompt 1 resumed from prompt 0's cached prefix pages
    assert eng.core.pool.stats["cached_tokens"] >= SMALL["shared"]
    exact, ties, total, gaps = chip_smoke.teacher_forced_agreement(
        model, params, prompts, outputs)
    assert total == MAX_NEW * len(prompts)
    assert (exact, ties, gaps) == (total, 0, [])
    # on the CPU the compiled decode step holds no Mosaic kernel —
    # check (b) can only pass on the chip
    assert not chip_smoke.decode_holds_kernel(eng.core.runner)

    _route_paged_attention(monkeypatch, "ref")
    ref_outputs, _ = _serve(model, params, prompts)
    assert ref_outputs == outputs


def test_agreement_counts_mismatches(reduced):
    model, params = reduced
    prompts = chip_smoke.make_prompts(2, model.cfg.vocab_size, n=3,
                                      **SMALL)
    outputs, _ = _serve(model, params, prompts)
    flipped = [[(t + 1) % model.cfg.vocab_size for t in o]
               for o in outputs]
    exact, ties, total, gaps = chip_smoke.teacher_forced_agreement(
        model, params, prompts, flipped)
    assert (exact, ties, total) == (0, 0, 3 * MAX_NEW)
    assert len(gaps) == total and min(gaps) > 0


def test_agreement_counts_exact_logit_ties(reduced):
    """A served token whose logit equals the reference maximum but
    loses argmax's lowest-index tie-break is counted as a tie, not a
    match.  Qwen3 ties its embeddings, so copying token i's embedding
    row to an unused token j gives j exactly i's logits."""
    model, params = reduced
    prompts = chip_smoke.make_prompts(2, model.cfg.vocab_size, n=3,
                                      **SMALL)
    outputs, _ = _serve(model, params, prompts)
    used = {t for seq in prompts + outputs for t in seq}
    i = outputs[0][0]
    j = max(t for t in range(i + 1, model.cfg.vocab_size)
            if t not in used)
    twin = dict(params, embed=params["embed"].at[j].set(params["embed"][i]))
    served = [list(o) for o in outputs]
    served[0][0] = j
    exact, ties, total, gaps = chip_smoke.teacher_forced_agreement(
        model, twin, prompts, served)
    assert (exact, ties, total) == (total - 1, 1, 3 * MAX_NEW)
    assert gaps == [0.0]


def test_result_line_format():
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"
    line = chip_smoke.result_line([Dev()])
    assert json.loads(line) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert "\n" not in line


def test_cli_refuses_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(ROOT,
                                                       "chip_smoke.py")],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr


def test_tp_phase_on_forced_host_devices():
    """The --chips 4 phase on four forced CPU devices: TP greedy tokens
    equal the one-device engine's, one psum per layer in decode."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    snippet = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {ROOT!r})
        import chip_smoke
        from repro.launch.mesh import make_mesh
        from repro.serving.worker import build_serving_model
        model, params = build_serving_model(chip_smoke.ARCH)
        prompts = chip_smoke.make_prompts(
            0, model.cfg.vocab_size, lengths=(12, 60), shared=24,
            chunk=32)
        kw = dict(page_size=8, prefill_chunk=32, max_len=96)
        outs = []
        for mesh in (None, make_mesh((4,), ("model",))):
            eng = chip_smoke.start_engine(model, params, mesh=mesh, **kw)
            outs.append(chip_smoke.serve(eng, prompts, max_new=8))
            eng.shutdown()
        assert outs[0] == outs[1], outs
        counts = chip_smoke.tp_decode_psums(eng.core.runner)
        assert counts == {{"psum": model.cfg.n_layers}}, counts
        print("TP-SMOKE-OK")
    """)
    out = subprocess.run([sys.executable, "-c", snippet],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "TP-SMOKE-OK" in out.stdout
