#!/usr/bin/env python3
"""Chip smoke: serve qwen3-1.7b at its published widths on a TPU.

Drives the serving main path once — ``AsyncEngine`` → ``EngineCore`` →
``ModelRunner`` → paged KV cache → Pallas paged decode kernel — with
random bf16 weights from ``--seed``: 8 greedy requests, prompts of
64–400 tokens (two share a 256-token prefix), 256-token prefill chunks
(so both the one-shot and the resumed prefill run), 32 new tokens
each.  It then checks what came out:

(a) the default backend is a TPU — the script never falls back to the
    CPU;
(b) the compiled decode step holds a Mosaic kernel (``tpu_custom_call``),
    not the jnp reference;
(c) teacher-forced: the plain non-paged ``Model.forward`` over each
    prompt plus its generated tokens agrees with the engine's greedy
    tokens at >= 0.95 of the generated positions — its argmax, or a
    logit within bf16 resolution of it (see
    :func:`teacher_forced_agreement`; exact matches are printed too).

``--chips 4`` runs only the tensor-parallel path and what it is
compared with: the same requests on a 4-way ``model`` mesh against
the one-chip engine in the same process.  Greedy tokens must be
identical (the head merge is exact) and the decode body must hold
exactly one psum per layer.

Lines before the last are smoke output, not metrics.  The last line
is one JSON object: ``{"ok": true, "device": {...}}``.  Any failed
check exits non-zero without it.

    python chip_smoke.py [--seed 0] [--chips 4]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen3-1.7b"
N_REQUESTS = 8
PROMPT_LEN = (64, 400)
SHARED_PREFIX = 256
PREFILL_CHUNK = 256
PAGE_SIZE = 16
MAX_LEN = 1024
MAX_NEW = 32
AGREEMENT_BOUND = 0.95


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def make_prompts(seed: int, vocab: int, *, n: int = N_REQUESTS,
                 lengths=PROMPT_LEN, shared: int = SHARED_PREFIX,
                 chunk: int = PREFILL_CHUNK):
    """``n`` token prompts from ``seed``: lengths uniform in ``lengths``;
    prompts 0 and 1 are longer than ``shared`` and share their first
    ``shared`` tokens (chunked, prefix-cached), prompt 2 fits one
    ``chunk`` (one-shot prefill)."""
    rng = np.random.default_rng(seed)
    lo, hi = lengths
    lens = rng.integers(lo, hi + 1, size=n)
    lens[:2] = rng.integers(max(shared, chunk) + 1, hi + 1, size=2)
    lens[2] = rng.integers(lo, min(chunk, hi) + 1)
    prompts = [rng.integers(0, vocab, size=int(m)).tolist() for m in lens]
    prompts[1][:shared] = prompts[0][:shared]
    return prompts


def start_engine(model, params, *, n_slots: int = N_REQUESTS, mesh=None,
                 page_size: int = PAGE_SIZE,
                 prefill_chunk: int = PREFILL_CHUNK,
                 max_len: int = MAX_LEN):
    from repro.serving import AsyncEngine
    return AsyncEngine(model, params, max_len=max_len, max_running=n_slots,
                       page_size=page_size, prefill_chunk=prefill_chunk,
                       mesh=mesh)


def serve(engine, prompts, *, max_new: int = MAX_NEW,
          timeout: float = 900.0):
    """Serve every prompt as a greedy request; return each request's
    generated tokens, in prompt order.  Prompt 0 goes first and the
    rest follow its first token, so its prefix pages are resident and
    prompt 1 resumes prefill from the prefix cache."""
    import threading

    from repro.serving import Request
    from repro.serving.sampler import SamplingParams
    sp = SamplingParams(temperature=0.0, max_new_tokens=max_new)
    first = threading.Event()
    handles = [engine.submit(Request(uid=0, prompt=list(prompts[0]),
                                     sampling=sp),
                             on_token=lambda _: first.set())]
    if not first.wait(timeout):
        raise TimeoutError(f"no first token within {timeout} s")
    handles += [engine.submit(Request(uid=i, prompt=list(p), sampling=sp))
                for i, p in enumerate(prompts[1:], 1)]
    return [list(engine.result(h, timeout=timeout).tokens)
            for h in handles]


def decode_holds_kernel(runner) -> bool:
    """Whether the compiled decode step holds a Mosaic kernel."""
    return "tpu_custom_call" in runner.decode_program_text()


def teacher_forced_agreement(model, params, prompts, outputs):
    """Replay each prompt + generated tokens through the plain
    non-paged ``Model.forward``: position ``len(prompt) - 1 + j`` should
    argmax to ``outputs[j]``.  Returns (exact matches, ties, total,
    reference logit gaps at the misses).  A miss is a tie when the
    served token's reference logit is within the model dtype's
    resolution of the maximum (``eps * |max logit|``): activations held
    in bf16 cannot order two logits closer than that, and random
    weights put such near-ties at a few percent of positions."""
    import jax
    import jax.numpy as jnp
    eps = float(jnp.finfo(model.cfg.dtype).eps)

    def scored(p, t):
        logits = model.forward(p, {"tokens": t})[0][0]
        top = jnp.max(logits, axis=-1)[:-1]
        served = jnp.take_along_axis(
            logits[:-1], t[0, 1:, None], axis=-1)[:, 0]
        return (jnp.argmax(logits, axis=-1)[:-1], top - served,
                eps * jnp.abs(top))

    width = max(len(p) + len(o) for p, o in zip(prompts, outputs))
    width = -(-width // 64) * 64          # one compiled shape for all
    scored = jax.jit(scored)
    exact = ties = total = 0
    gaps = []
    for p, o in zip(prompts, outputs):
        seq = np.zeros((1, width), np.int32)
        seq[0, :len(p) + len(o)] = p + o
        pred, gap, tol = (np.asarray(a) for a in scored(params, seq))
        at = slice(len(p) - 1, len(p) - 1 + len(o))
        miss = pred[at] != np.asarray(o)
        exact += int(np.sum(~miss))
        ties += int(np.sum(gap[at][miss] <= tol[at][miss]))
        total += len(o)
        gaps += gap[at][miss].tolist()
    return exact, ties, total, gaps


def tp_decode_psums(runner):
    """Collective primitives in the tensor-parallel decode body."""
    import jax.numpy as jnp

    from repro.core.tp import collective_ops_in
    n = runner.max_running
    return collective_ops_in(runner.tp_raw_decode, runner.params,
                             runner.cache, jnp.zeros((n, 1), jnp.int32),
                             jnp.zeros((n,), jnp.int32))


def result_line(devices) -> str:
    d = devices[0]
    return json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}})


class CompileLog:
    """Backend compile seconds per compiled program, from JAX's
    monitoring events (persistent-cache hits included: their time is
    the cache read)."""

    def __init__(self) -> None:
        import jax
        self.entries = []
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.entries.append((kw.get("fun_name", "?"), secs))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def report(self, label: str, *, min_secs: float = 1.0) -> None:
        for name, secs in self.entries:
            if secs >= min_secs:
                say(f"compile {secs:.2f} s {name}")
        total = sum(s for _, s in self.entries)
        say(f"{label}: {len(self.entries)} programs compiled in "
            f"{total:.2f} s, {self.cache_hits} persistent-cache hits")
        self.entries.clear()


def _one_chip(model, params, prompts, log: CompileLog) -> None:
    t0 = time.perf_counter()
    eng = start_engine(model, params)
    try:
        outputs = serve(eng, prompts)
        say(f"served {len(outputs)} requests, "
            f"{sum(map(len, outputs))} tokens in "
            f"{time.perf_counter() - t0:.1f} s (compiles included)")
        st = eng.core.pool.stats
        say(f"kv pool: {st['fresh_pages']} pages allocated, "
            f"{st['cached_tokens']} prompt tokens from the prefix cache")
        if not decode_holds_kernel(eng.core.runner):
            raise SmokeFailure("decode step holds no tpu_custom_call: "
                               "the Pallas paged kernel did not run")
        say("decode step holds tpu_custom_call (Pallas paged kernel)")
    finally:
        eng.shutdown()
    log.report("serving")
    exact, ties, total, gaps = teacher_forced_agreement(
        model, params, prompts, outputs)
    log.report("reference")
    rate = (exact + ties) / total
    say(f"teacher-forced argmax matches {exact}/{total} = "
        f"{exact / total:.4f}; reference logit gap at the misses "
        f"{sorted(round(g, 4) for g in gaps)}")
    say(f"teacher-forced agreement {exact + ties}/{total} = {rate:.4f} "
        f"counting {ties} misses within bf16 resolution as ties "
        f"(bound {AGREEMENT_BOUND})")
    if rate < AGREEMENT_BOUND:
        raise SmokeFailure(f"teacher-forced agreement {rate:.4f} under "
                           f"{AGREEMENT_BOUND}")


def _four_chips(model, params, prompts, log: CompileLog, n: int) -> None:
    import jax

    from repro.launch.mesh import make_mesh
    if len(jax.devices()) < n:
        raise SmokeFailure(f"--chips {n} on a host with "
                           f"{len(jax.devices())} chips")
    eng = start_engine(model, params)
    try:
        want = serve(eng, prompts)
    finally:
        eng.shutdown()
    del eng
    log.report("one-chip engine")
    eng = start_engine(model, params, mesh=make_mesh((n,), ("model",)))
    try:
        got = serve(eng, prompts)
        runner = eng.core.runner
        if not decode_holds_kernel(runner):
            raise SmokeFailure("TP decode step holds no tpu_custom_call")
        counts = tp_decode_psums(runner)
    finally:
        eng.shutdown()
    log.report(f"{n}-chip engine")
    say(f"{n}-way 'model' mesh: {sum(map(len, got))} tokens; decode "
        f"collectives {counts}")
    if got != want:
        bad = [i for i, (g, w) in enumerate(zip(got, want)) if g != w]
        raise SmokeFailure(f"TP greedy tokens differ from one chip on "
                           f"requests {bad}")
    say("greedy tokens identical to the one-chip engine")
    if counts != {"psum": model.cfg.n_layers}:
        raise SmokeFailure(f"decode collectives {counts}, want one psum "
                           f"per layer ({model.cfg.n_layers})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: tensor-parallel serving against one chip")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (default backend is "
              f"{devices[0].platform}); refusing to run", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.worker import build_serving_model
    say("lines marked 'smoke:' are bring-up output, not metrics")
    say(f"compile cache {enable_compile_cache()}")
    say(f"device {devices[0].device_kind} x{len(devices)}")
    log = CompileLog()
    model, params = build_serving_model(ARCH, full_config=True,
                                        seed=args.seed)
    cfg = model.cfg
    say(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads x {cfg.resolved_head_dim}, "
        f"vocab {cfg.vocab_size}, {np.dtype(cfg.dtype).name}, "
        f"{cfg.param_count() / 1e9:.2f} B params (random, seed "
        f"{args.seed})")
    log.report("weight init")
    prompts = make_prompts(args.seed, cfg.vocab_size)
    say(f"prompt lengths {[len(p) for p in prompts]}")
    try:
        if args.chips == 1:
            _one_chip(model, params, prompts, log)
        else:
            _four_chips(model, params, prompts, log, args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        stats = devices[0].memory_stats() or {}
        say(f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'n/a')}")
    print(result_line(devices), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
