"""Granite's four scalars through the serving engine, against the plain
reference ``bench/reference/granite.py``.

A tiny Granite (2 layers, d 64, 4 query and 2 kv heads) with the
published scalars and seeded random float32 weights is served greedily
by the continuous engine with chunked prefill and speculative decoding
on: every logit the runner returns — fresh and resumed prefill chunks,
paged decode steps, speculative verify columns — is compared with the
reference's logit at the same position of the same sequence.
"""

import math
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import weights as bench_weights  # noqa: E402
from bench.check import load_reference  # noqa: E402

from repro.models import ModelConfig, build_model  # noqa: E402
from repro.quant.policy import QuantPolicy  # noqa: E402
from repro.serving import (ContinuousServingEngine, Request,  # noqa: E402
                           SamplingParams, VirtualClock)

PUBLISHED = {"attention_multiplier": 0.0078125, "embedding_multiplier": 12.0,
             "residual_multiplier": 0.22, "logits_scaling": 16.0}
NEUTRAL = {"attention_multiplier": 16 ** -0.5, "embedding_multiplier": 1.0,
           "residual_multiplier": 1.0, "logits_scaling": 1.0}
TINY = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 256, "rms_norm_eps": 1e-5, "rope_theta": 10_000.0,
        "tie_word_embeddings": True, "torch_dtype": "float32", **PUBLISHED}
SEED = 20_241_021
#: float32 on both sides, default (full) matmul precision on the CPU:
#: what remains is summation order — blockwise online softmax against
#: one softmax, per-chunk against whole-sequence matmuls — ~1e-8 on
#: logits whose spread is ~0.01 after the division by 16
TOL = 1e-6
#: int8 KV pages round each K/V row to 1/254 of its largest entry
#: (~0.4%), which moves these logits by ~1e-4
TOL_INT8 = 1e-3
#: prefill chunks of 8 tokens: the 21-token prompt is prefilled in
#: three chunks (the later two resumed), the 8-token one in one fresh
#: chunk; each prompt repeats its head, so prompt lookup drafts and the
#: first decode steps run as speculative verify steps
CHUNK, MAX_NEW, LOOKAHEAD = 8, 6, 3
KINDS = ["prefill_fresh", "prefill_resumed", "decode", "verify"]


def program_config(conf):
    return ModelConfig(
        name="granite-tiny", arch_type="dense",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        rope_theta=conf["rope_theta"], norm_eps=conf["rms_norm_eps"],
        tie_embeddings=conf["tie_word_embeddings"], dtype=jnp.float32,
        **{k: conf[k] for k in PUBLISHED})


def prompts():
    rng = np.random.default_rng(SEED)
    out = []
    for n, again in ((13, 8), (5, 3)):
        head = rng.integers(0, TINY["vocab_size"], n).tolist()
        out.append(head + head[:again])     # 21 and 8 tokens
    return out


def _serve(conf, params, **engine):
    """Serve ``prompts()`` and record every runner call with its
    logits: (kind, uid, first row the logits predict from, logits)."""
    eng = ContinuousServingEngine(
        build_model(program_config(conf)), params, max_len=64,
        max_running=2, page_size=4, prefill_chunk=CHUNK,
        spec_decode=LOOKAHEAD, clock=VirtualClock(), **engine)
    core, runner = eng.core, eng.core.runner
    calls = []
    lane_uid = {}

    def prefill_chunk(tokens, *, slot, start, fresh):
        lg = prefill_chunk.inner(tokens, slot=slot, start=start,
                                 fresh=fresh)
        lane_uid[slot] = core.scheduler.running[slot].uid
        kind = "prefill_fresh" if fresh else "prefill_resumed"
        calls.append((kind, lane_uid[slot], start + len(tokens) - 1,
                      np.asarray(lg)[0, -1:]))
        return lg

    def decode(fed, pos):
        lg = decode.inner(fed, pos)
        for b in np.flatnonzero(np.asarray(pos) >= 0):
            calls.append(("decode", core.scheduler.running[b].uid,
                          int(pos[b]), np.asarray(lg)[b, :1]))
        return lg

    def verify(fed, pos, n_fed):
        lg = verify.inner(fed, pos, n_fed)
        for b in np.flatnonzero(np.asarray(pos) >= 0):
            calls.append(("verify", core.scheduler.running[b].uid,
                          int(pos[b]), np.asarray(lg)[b, :int(n_fed[b])]))
        return lg

    for fn in (prefill_chunk, decode, verify):
        fn.inner = getattr(runner, fn.__name__)
        setattr(runner, fn.__name__, fn)
    reqs = [Request(uid=i, prompt=p,
                    sampling=SamplingParams(max_new_tokens=MAX_NEW))
            for i, p in enumerate(prompts())]
    done = {c.uid: reqs[c.uid].prompt + list(c.tokens)
            for c in eng.generate(reqs)}
    return calls, done


@pytest.fixture(scope="module")
def served():
    params = bench_weights.make(TINY, SEED)
    calls, seqs = _serve(TINY, params)
    return params, calls, seqs


def reference_logits(params, conf, seqs):
    """The reference's logits at every row of every served sequence."""
    ref = load_reference("granite")
    return {uid: np.asarray(ref.logits_at(params, conf, np.asarray(s),
                                          np.arange(len(s) - 1)))
            for uid, s in seqs.items()}


def worst_gap(calls, ref, kinds):
    gaps = [np.max(np.abs(lg - ref[uid][row:row + len(lg)]))
            for kind, uid, row, lg in calls if kind in kinds]
    assert gaps, f"the engine ran no {kinds} call"
    return float(max(gaps))


@pytest.mark.parametrize("kind", KINDS)
def test_engine_logits_match_reference(served, kind):
    params, calls, seqs = served
    ref = reference_logits(params, TINY, seqs)
    assert worst_gap(calls, ref, {kind}) < TOL


@pytest.mark.parametrize("dropped", sorted(PUBLISHED))
def test_dropping_one_scalar_fails_the_comparison(served, dropped):
    """The reference without one of the scalars is far outside either
    tolerance, on every kind of call together."""
    params, calls, seqs = served
    conf = dict(TINY, **{dropped: NEUTRAL[dropped]})
    ref = reference_logits(params, conf, seqs)
    assert worst_gap(calls, ref, set(KINDS)) > 10 * TOL_INT8


def test_int8_kv_pages_apply_the_scalars():
    params = bench_weights.make(TINY, SEED)
    calls, seqs = _serve(TINY, params,
                         quant=QuantPolicy(kv_dtype="int8"))
    ref = reference_logits(params, TINY, seqs)
    assert worst_gap(calls, ref, set(KINDS)) < TOL_INT8


def test_tensor_parallel_applies_the_scalars_with_no_new_collective():
    """Two head shards serve the same tokens and decode logits as one,
    and the decode program's only collectives are one psum a layer."""
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"),
               XLA_FLAGS=os.environ.get("XLA_FLAGS", "")
               + " --xla_force_host_platform_device_count=2")
    child = textwrap.dedent(f"""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.tp import collective_ops_in
        from repro.launch.mesh import make_mesh
        from repro.models import ModelConfig, build_model
        from repro.serving import (ContinuousServingEngine, Request,
                                   SamplingParams)
        cfg = ModelConfig(name="granite-tiny", arch_type="dense",
                          n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                          d_ff=128, vocab_size=256, dtype=jnp.float32,
                          **{PUBLISHED!r})
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        reqs = [Request(uid=i, prompt=p,
                        sampling=SamplingParams(max_new_tokens=6))
                for i, p in enumerate({prompts()!r})]

        def run(mesh=None, n_nodes=1):
            eng = ContinuousServingEngine(
                model, params, max_len=64, max_running=2, page_size=4,
                prefill_chunk=8, spec_decode=3, mesh=mesh, n_nodes=n_nodes)
            toks = [c.tokens for c in eng.generate(reqs)]
            lg = eng.core.runner.decode(np.array([[5], [7]], np.int32),
                                        np.array([3, -1], np.int32))
            return eng, toks, np.asarray(lg)

        _, want, want_lg = run()
        eng, got, got_lg = run(make_mesh((2,), ("model",)), n_nodes=2)
        assert got == want, (got, want)
        np.testing.assert_allclose(got_lg, want_lg, rtol=0, atol=1e-6)
        r = eng.core.runner
        counts = collective_ops_in(r.tp_raw_decode, r.params, r.cache,
                                   jnp.ones((2, 1), jnp.int32),
                                   jnp.zeros((2,), jnp.int32))
        assert counts == {{"psum": cfg.n_layers}}, counts
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", child], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr[-3000:]


def test_neutral_granite_reference_is_the_dense_reference():
    conf = dict(TINY, **NEUTRAL)
    params = bench_weights.make(conf, SEED)
    tokens = np.asarray(prompts()[0])
    rows = np.arange(len(tokens) - 1)
    got = load_reference("granite").logits_at(params, conf, tokens, rows)
    want = load_reference("dense").logits_at(params, conf, tokens, rows)
    # float32 at HIGHEST on both sides and the same operations: the
    # neutral scalars multiply or divide by 1 and scale by 1/sqrt(D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-6)


@pytest.mark.parametrize("scale", ["inv_d", "inv_sqrt_d"])
def test_paged_kernel_scale_matches_oracle(scale):
    from repro.kernels.decode_attention import paged_decode_attention
    from repro.kernels.ref import paged_decode_attention_ref
    Hkv, G, D, ps, mp = 2, 2, 16, 4, 4
    s = 1.0 / D if scale == "inv_d" else 1.0 / math.sqrt(D)
    lens = np.array([0, 3, ps * mp, 9], np.int32)
    B, P = len(lens), 1 + len(lens) * mp
    rng = np.random.default_rng(7)
    bt = (1 + rng.permutation(B * mp)).reshape(B, mp).astype(np.int32)
    q = jnp.asarray(4 * rng.normal(size=(B, Hkv, G, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, ps, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, ps, Hkv, D)), jnp.float32)
    ker = paged_decode_attention(q, kp, vp, bt, lens, 0, scale=s,
                                 interpret=True)
    ref = paged_decode_attention_ref(q, kp, vp, bt, lens, 0, scale=s)
    # float32 throughout; online softmax against one softmax
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    other = paged_decode_attention_ref(
        q, kp, vp, bt, lens, 0, scale=1.0 / math.sqrt(D) if
        scale == "inv_d" else 1.0 / D)
    assert np.max(np.abs(np.asarray(other) - np.asarray(ker))) > 1e-2


def test_src_config_is_the_published_model():
    """``repro.configs``' granite-3-8b states the published model the
    benchmark's stage configuration cuts from."""
    import json

    from repro.configs import get_config
    cfg = get_config("granite-3-8b")
    stage = json.loads((REPO / "bench" / "configs"
                        / "granite-3-8b-pp2.json").read_text())
    assert cfg.source == "hf:ibm-granite/granite-3.0-8b-base"
    assert cfg.n_layers == stage["published"]["num_hidden_layers"] == 40
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.d_ff, cfg.vocab_size) == (
        stage["hidden_size"], stage["num_attention_heads"],
        stage["num_key_value_heads"], stage["head_dim"],
        stage["intermediate_size"], stage["vocab_size"])
    assert cfg.tie_embeddings and cfg.norm_eps == stage["rms_norm_eps"]
    for key in PUBLISHED:
        assert getattr(cfg, key) == stage["published"][key] == \
            PUBLISHED[key], key
    # 8.17 B parameters with the tied head, as published
    assert round(cfg.param_count() / 1e9, 2) == 8.17

