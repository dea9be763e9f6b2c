"""Serving launcher: ``python -m repro.launch.serve --arch <id>``.

Brings up the decoding frontend on a REDUCED variant of the assigned
architecture (CPU host) — or, with ``--full-config``, on the arch at
its published widths and dtype (accelerator host, random weights) —
optionally warm-trains a reduced model briefly so greedy output isn't
pure noise, then serves a batch of byte-level prompts and prints the
throughput report (the paper's §4 measurement protocol).

``--engine bucket`` (default) is the sequential length-bucket baseline;
``--engine continuous`` runs the paged-KV continuous-batching engine
(uniform self-attention archs only — the paged cache has no recurrent/
cross-attention state yet); ``--engine async`` serves the same stack
through the live ``AsyncEngine`` (submit/stream on a background
stepper thread) — add ``--interactive`` for a stdin demo that streams
each prompt's tokens as they are sampled.

Observability (paged engines): ``--metrics-json PATH`` writes the
metrics registry snapshot on exit, ``--trace PATH`` records per-request
trace spans as JSONL, ``--stats-every SECS`` prints a periodic metrics
line while the async engine serves (``docs/observability.md``).

Network serving (``docs/serving.md`` "HTTP serving front-end"):
``--http`` serves ``/v1/completions`` (SSE streaming) + ``/healthz`` +
``/metrics`` instead of running the batch demo.  ``--replicas 0``
(default) serves the in-process ``AsyncEngine``; ``--replicas N``
spawns N ``repro.serving.worker`` subprocesses under a supervisor and
routes across them with prefix-affinity placement
(``repro.serving.router``).  ``--port 0`` picks a free port;
``--port-file PATH`` writes the bound port for scripts
(``tools/check.sh --smoke``).

Examples:
    python -m repro.launch.serve --arch gemma3-1b --max-new 24
    python -m repro.launch.serve --arch qwen3-1.7b --engine continuous \\
        --max-running 4 --page-size 16
    python -m repro.launch.serve --arch qwen3-1.7b --engine async \\
        --interactive --warmup-steps 80
    python -m repro.launch.serve --arch recurrentgemma-2b \\
        --prompt "the scheduler binds" --temperature 0.7
    python -m repro.launch.serve --arch tiny --engine async --http \\
        --replicas 2 --port 8080
"""

import argparse
import sys


def stream_interactive(eng, handle, write, *, decode=None,
                       timeout: float = 300.0) -> str:
    """Stream one interactive request through ``write``; returns
    ``"finished"`` / ``"failed"`` / ``"cancelled"``.

    A handle that lands FAILED raises ``AsyncEngineError`` out of
    ``stream()`` with the real error chained as ``__cause__`` — the
    interactive loop used to crash on it and drop the reason; here the
    chained cause is printed and the session keeps going
    (``tests/test_async_serving.py``).
    """
    from ..serving.async_engine import AsyncEngineError, RequestState
    decode = decode if decode is not None else str
    try:
        for t in eng.stream(handle, timeout=timeout):
            write(decode(t))
    except AsyncEngineError as e:
        cause = e.__cause__
        write(f"\n[request failed: {e}"
              + (f" — caused by {type(cause).__name__}: {cause}"
                 if cause is not None else "") + "]\n")
        return "failed"
    except TimeoutError as e:
        eng.cancel(handle)
        write(f"\n[request timed out: {e}]\n")
        return "failed"
    if handle.state is RequestState.CANCELLED:
        write("\n[request cancelled]\n")
        return "cancelled"
    write("\n")
    return "finished"


def _serve_http(fe, *, port_file=None, supervisor=None) -> int:
    """Run a started frontend until SIGTERM/SIGINT, then drain the
    backend (and, behind a router, the worker fleet)."""
    import signal
    import threading

    from ..serving import faults
    faults.load_env()       # REPRO_FAULTS chaos harness (no-op unset)
    if port_file:
        with open(port_file, "w") as f:
            f.write(str(fe.port))
    print(f"serving http on {fe.url} "
          "(/v1/completions /healthz /metrics)", flush=True)
    stop = threading.Event()
    for s in (signal.SIGTERM, signal.SIGINT):
        signal.signal(s, lambda *_: stop.set())
    stop.wait()
    fe.close(shutdown_backend=True)
    if supervisor is not None:
        supervisor.shutdown()
    print("http serving stopped", flush=True)
    return 0


def _serve_replicated(args) -> int:
    """``--http --replicas N``: front-door process holds only the
    supervisor + router + frontend — no model is built here; each
    worker subprocess builds its own engine + page pool."""
    from ..data.tokenizer import ByteTokenizer
    from ..serving.http import HttpFrontend
    from ..serving.router import Router
    from ..serving.supervisor import Supervisor
    worker_args = ["--arch", args.arch, "--max-running",
                   str(args.max_running), "--page-size",
                   str(args.page_size), "--seed", "0"]
    if args.n_pages is not None:
        worker_args += ["--n-pages", str(args.n_pages)]
    if args.prefill_chunk is not None:
        worker_args += ["--prefill-chunk", str(args.prefill_chunk)]
    if args.no_prefix_cache:
        worker_args += ["--no-prefix-cache"]
    if args.quant != "none":
        worker_args += ["--quant", args.quant]
    if args.kv_dtype != "fp32":
        worker_args += ["--kv-dtype", args.kv_dtype]
    if args.spec_decode:
        worker_args += ["--spec-decode", str(args.spec_decode)]
    sup = Supervisor(args.replicas, worker_args, host=args.host,
                     max_respawns=args.max_respawns)
    print(f"starting {args.replicas} engine workers "
          f"(--arch {args.arch}) ...", flush=True)
    clients = sup.start()
    router = Router(clients, page_size=args.page_size,
                    breaker_threshold=args.breaker_threshold)
    # the self-healing loop: death drains the replica from the ring;
    # a successful respawn re-admits it (docs/serving.md)
    sup.on_death = lambda rid, rc: router.mark_dead(rid)
    sup.on_respawn = lambda rid, client: router.readmit(rid, client)
    for rid, c in sorted(clients.items()):
        print(f"  worker {rid}: {c.describe()}", flush=True)
    fe = HttpFrontend(router, tokenizer=ByteTokenizer(), host=args.host,
                      port=args.port, max_inflight=args.max_inflight,
                      max_queue_depth=args.max_queue_depth).start()
    return _serve_http(fe, port_file=args.port_file, supervisor=sup)


def _print_shard_stats(pool) -> None:
    """Per-shard KV pool residency under --tp-shards: every shard holds
    its head slice of every page."""
    live = pool.n_live() * pool.cfg.page_shard_bytes
    print("tp pool: "
          + ", ".join(f"shard{s} {live / 1024:.0f}/{b / 1024:.0f} KiB live"
                      for s, b in sorted(
                          pool.capacity_bytes_per_shard().items())))


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--prompt", action="append", default=None)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--engine", choices=("bucket", "continuous", "async"),
                    default="bucket")
    ap.add_argument("--interactive", action="store_true",
                    help="async engine: read prompts from stdin and "
                         "stream tokens as they are sampled")
    ap.add_argument("--max-running", type=int, default=4,
                    help="continuous engine: running-batch slots")
    ap.add_argument("--page-size", type=int, default=16,
                    help="continuous engine: KV page token slots")
    ap.add_argument("--n-pages", type=int, default=None,
                    help="continuous engine: KV pool pages "
                         "(default: no-preemption sizing)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="continuous engine: prefill at most this many "
                         "prompt tokens per step (default: one-shot)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="continuous engine: disable prompt-prefix "
                         "page sharing")
    ap.add_argument("--quant", choices=("none", "q4"), default="none",
                    help="continuous/async engines: weight format — "
                         "'q4' packs attention/MLP projections to Q4_0 "
                         "at load (docs/quantization.md)")
    ap.add_argument("--kv-dtype", choices=("fp32", "int8"),
                    default="fp32",
                    help="continuous/async engines: KV page format — "
                         "'int8' stores quantized pages with per-row "
                         "scales, fitting >=1.9x the pages in the same "
                         "pool bytes (docs/quantization.md)")
    ap.add_argument("--spec-decode", type=int, default=0, metavar="K",
                    help="continuous/async engines: self-speculative "
                         "decoding — draft up to K tokens per step by "
                         "prompt lookup and verify them in one batched "
                         "forward; greedy output stays byte-identical "
                         "to K=0 (docs/serving.md)")
    ap.add_argument("--tp-shards", type=int, default=1,
                    help="continuous/async engines: tensor-parallel "
                         "shards — forces that many host devices on "
                         "the CPU, head-shards the KV pages over the "
                         "mesh's 'model' axis")
    ap.add_argument("--warmup-steps", type=int, default=40,
                    help="brief LM warm-up so outputs aren't noise "
                         "(0 = random weights)")
    ap.add_argument("--full-config", action="store_true",
                    help="serve the arch at its published widths and "
                         "dtype (accelerator hosts; needs "
                         "--warmup-steps 0) instead of its reduced "
                         "float32 variant")
    ap.add_argument("--metrics-json", metavar="PATH", default=None,
                    help="paged engines: write the metrics registry "
                         "snapshot (JSON, repro.obs schema) on exit")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="paged engines: record per-request trace "
                         "spans and write them as JSONL on exit")
    ap.add_argument("--stats-every", type=float, default=0.0,
                    metavar="SECS",
                    help="async engine: print a one-line metrics "
                         "summary every SECS seconds while serving")
    ap.add_argument("--http", action="store_true",
                    help="serve /v1/completions + /healthz + /metrics "
                         "over HTTP instead of the batch demo "
                         "(async engine)")
    ap.add_argument("--host", default="127.0.0.1",
                    help="--http: bind address")
    ap.add_argument("--port", type=int, default=0,
                    help="--http: bind port (0 picks a free one)")
    ap.add_argument("--port-file", metavar="PATH", default=None,
                    help="--http: write the bound port here (scripts)")
    ap.add_argument("--replicas", type=int, default=0,
                    help="--http: engine-worker subprocesses behind a "
                         "prefix-affinity router (0 = serve the "
                         "in-process engine)")
    ap.add_argument("--max-respawns", type=int, default=2,
                    help="--replicas: restarts the supervisor grants "
                         "each dead worker before it stays dead "
                         "(0 disables self-healing)")
    ap.add_argument("--max-inflight", type=int, default=None,
                    help="--http: admission cap — requests in flight "
                         "at the frontend; excess is shed with 429 + "
                         "Retry-After (docs/robustness.md)")
    ap.add_argument("--max-queue-depth", type=int, default=None,
                    help="--http: shed with 429 while the scheduler "
                         "queue is this deep (in-process engine only)")
    ap.add_argument("--breaker-threshold", type=int, default=3,
                    help="--replicas: consecutive worker failures that "
                         "open the router's circuit breaker")
    args = ap.parse_args()

    if args.engine == "bucket" and (args.metrics_json or args.trace
                                    or args.stats_every):
        ap.error("--metrics-json/--trace/--stats-every report the paged "
                 "serving stack; use --engine continuous or async")
    if args.engine == "bucket" and (args.quant != "none"
                                    or args.kv_dtype != "fp32"):
        ap.error("--quant/--kv-dtype serve through the paged engines; "
                 "use --engine continuous or async")
    if args.engine == "bucket" and args.spec_decode:
        ap.error("--spec-decode serves through the paged engines; "
                 "use --engine continuous or async")
    if args.spec_decode < 0:
        ap.error("--spec-decode must be >= 0")
    if args.replicas and not args.http:
        ap.error("--replicas needs --http")
    if args.full_config and args.warmup_steps:
        ap.error("--full-config serves random weights: AdamW warm-up "
                 "of the full model is training, not serving — pass "
                 "--warmup-steps 0")
    if args.full_config and args.replicas:
        ap.error("--replicas workers serve reduced variants; "
                 "--full-config applies to --replicas 0")
    if args.max_respawns < 0:
        ap.error("--max-respawns must be >= 0")
    if args.http:
        if args.engine != "async":
            ap.error("--http serves through the async engine; add "
                     "--engine async")
        if args.interactive:
            ap.error("--http and --interactive are exclusive")
        if args.replicas:
            if args.tp_shards > 1:
                ap.error("--replicas spawns single-shard workers; "
                         "--tp-shards applies to --replicas 0")
            return _serve_replicated(args)

    import os
    import time

    if args.tp_shards > 1:
        # sizes the CPU backend only (an accelerator backend ignores
        # it); must land before the first jax import: the CPU device
        # count is fixed at backend initialisation
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.tp_shards}")

    import jax

    from ..configs import list_archs
    from ..data.pipeline import (PackedLMDataset, stub_frames,
                                 stub_image_embeds)
    from ..data.tokenizer import ByteTokenizer
    from ..serving import (AsyncEngine, ContinuousServingEngine, Request,
                           ServingEngine, throughput_report)
    from ..serving.sampler import SamplingParams
    from ..serving.worker import build_serving_model
    from ..training.loop import train
    from ..training.optimizer import AdamWConfig
    from .compile_cache import enable_compile_cache

    if args.arch != "tiny" and args.arch not in list_archs():
        ap.error(f"unknown arch; choose 'tiny' or one of {list_archs()}")
    enable_compile_cache()
    # 'tiny' is the benchmark suite's bench-tiny model: instant to
    # build, the smoke-test arch for --http
    model, params = build_serving_model(args.arch,
                                        full_config=args.full_config)
    cfg = model.cfg
    tok = ByteTokenizer()
    print(f"arch={cfg.name} "
          f"({'full' if args.full_config else 'reduced'}, "
          f"{cfg.param_count() / 1e6:.1f}M)")

    mesh = None
    if args.tp_shards > 1:
        if args.engine == "bucket":
            ap.error("--tp-shards serves through the paged engines; "
                     "use --engine continuous or async")
        from .mesh import make_mesh
        devs = jax.devices()
        if len(devs) < args.tp_shards:
            hint = ("XLA_FLAGS was set too late — is jax imported "
                    "before main()?" if devs[0].platform == "cpu" else
                    "this host has fewer chips")
            ap.error(f"{len(devs)} {devs[0].platform} devices for "
                     f"--tp-shards {args.tp_shards} ({hint})")
        mesh = make_mesh((args.tp_shards,), ("model",))
        print(f"tp mesh: {args.tp_shards}-way 'model' axis over "
              f"{devs[0].platform} devices")

    if args.warmup_steps:
        print(f"warm-up training ({args.warmup_steps} steps) ...")
        ds = PackedLMDataset(seq_len=64, n_docs=1000,
                             vocab_size=cfg.vocab_size)

        def extra_fn(step, bs):
            extra = {}
            if cfg.is_encoder_decoder:
                extra["frames"] = stub_frames(bs, cfg.n_audio_frames,
                                              cfg.d_model, seed=step)
            if cfg.cross_attn_every:
                extra["image_embeds"] = stub_image_embeds(
                    bs, cfg.n_image_tokens, cfg.d_model, seed=step)
            return extra

        params, _, _ = train(model, params, ds.batches(8, extra_fn=extra_fn),
                             AdamWConfig(lr=2e-3, warmup_steps=5,
                                         total_steps=args.warmup_steps),
                             steps=args.warmup_steps, log_every=20)

    prompts = args.prompt or ["the scheduler binds", "a numa node",
                              "the kv cache streams", "one thread gathers"]
    sp = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                        max_new_tokens=args.max_new)
    reqs = []
    for i, p in enumerate(prompts):
        extra = {}
        if cfg.is_encoder_decoder:
            extra["frames"] = stub_frames(1, cfg.n_audio_frames,
                                          cfg.d_model)[0]
        if cfg.cross_attn_every:
            extra["image_embeds"] = stub_image_embeds(
                1, cfg.n_image_tokens, cfg.d_model)[0]
        reqs.append(Request(uid=i, prompt=tok.encode(p), sampling=sp,
                            extra=extra))
    max_len = max(len(r.prompt) for r in reqs) + args.max_new + 8
    tracer = None
    if args.trace:
        from ..obs import RequestTracer
        tracer = RequestTracer()
    #: metrics the periodic --stats-every line summarises
    stat_names = ("serving.steps", "scheduler.running",
                  "scheduler.queue_depth", "scheduler.preemptions",
                  "serving.tokens.decode", "kv_pool.pages_free")
    quant = None
    if args.quant != "none" or args.kv_dtype != "fp32":
        from ..quant.policy import QuantPolicy
        quant = QuantPolicy(weights=args.quant, kv_dtype=args.kv_dtype)
        print(f"quant: weights={quant.weights} kv_dtype={quant.kv_dtype} "
              "(docs/quantization.md)")
    if args.engine == "async":
        eng = AsyncEngine(
            model, params, max_len=max(max_len, 256 + args.max_new)
            if (args.interactive or args.http) else max_len,
            max_running=args.max_running, page_size=args.page_size,
            n_pages=args.n_pages, prefill_chunk=args.prefill_chunk,
            prefix_cache=not args.no_prefix_cache, mesh=mesh, quant=quant,
            spec_decode=args.spec_decode, tracer=tracer)
        if args.http:        # --replicas 0: in-process engine over HTTP
            from ..serving.http import HttpFrontend
            fe = HttpFrontend(eng, tokenizer=tok, host=args.host,
                              port=args.port,
                              max_inflight=args.max_inflight,
                              max_queue_depth=args.max_queue_depth
                              ).start()
            return _serve_http(fe, port_file=args.port_file)
        if args.interactive:
            print("interactive async demo — one prompt per line, "
                  "empty line or EOF quits")
            while True:
                try:
                    line = input("> ")
                except EOFError:
                    break
                if not line.strip():
                    break
                handle = eng.submit(Request(uid=0,
                                            prompt=tok.encode(line),
                                            sampling=sp))
                stream_interactive(
                    eng, handle,
                    lambda s: print(s, end="", flush=True),
                    decode=lambda t: tok.decode([t]), timeout=300)
            eng.shutdown()
            return 0
        t_submit = []
        handles = []
        for r in reqs:          # live submission: all clients at once
            t_submit.append(time.perf_counter())
            handles.append(eng.submit(r))
        if args.stats_every:
            next_stat = time.perf_counter() + args.stats_every
            while not all(h.done for h in handles):
                time.sleep(min(0.05, args.stats_every))
                if time.perf_counter() >= next_stat:
                    print("stats:", eng.registry.stats_line(stat_names))
                    next_stat += args.stats_every
        comps = [eng.result(h, timeout=600) for h in handles]
        st = eng.core.pool.stats
        print(f"kv pool: {st['fresh_pages']} pages allocated, "
              f"{st['shared_pages']} shared, {st['cow_copies']} CoW, "
              f"{st['cached_tokens']} prompt tokens from cache, "
              f"{st['retention_hits']} retention hits")
        if mesh is not None:
            _print_shard_stats(eng.core.pool)
        ttft = sorted(c.t_first - ts for c, ts in zip(comps, t_submit))
        print(f"ttft: p50 {ttft[len(ttft) // 2] * 1e3:.1f} ms, "
              f"max {ttft[-1] * 1e3:.1f} ms")
        # TTFT decomposition: queue-wait (submit -> first slot) +
        # prefill (slot -> first token) — Completion.t_sched
        qw = sorted(c.t_sched - c.t0 for c in comps)
        print(f"queue-wait: p50 {qw[len(qw) // 2] * 1e3:.1f} ms, "
              f"max {qw[-1] * 1e3:.1f} ms")
        eng.shutdown()
    elif args.engine == "continuous":
        eng = ContinuousServingEngine(
            model, params, max_len=max_len, max_running=args.max_running,
            page_size=args.page_size, n_pages=args.n_pages,
            prefill_chunk=args.prefill_chunk,
            prefix_cache=not args.no_prefix_cache, mesh=mesh, quant=quant,
            spec_decode=args.spec_decode, tracer=tracer)
        comps = eng.generate(reqs)
        st = eng.pool.stats
        print(f"kv pool: {st['fresh_pages']} pages allocated, "
              f"{st['shared_pages']} shared, {st['cow_copies']} CoW, "
              f"{st['cached_tokens']} prompt tokens served from cache")
        if mesh is not None:
            _print_shard_stats(eng.pool)
    else:
        eng = ServingEngine(model, params, max_len=max_len)
        comps = eng.generate(reqs, max_batch=args.max_batch)
    for c, p in zip(comps, prompts):
        print(f"[{c.uid}] {p!r} -> {tok.decode(c.tokens)!r}")
    # async completions carry t0/t1 stamps; sync engines report their
    # own phase times
    phase = getattr(eng, "last_phase_s", None) or {}
    rep = throughput_report(comps, **phase)
    print("throughput:", {k: round(v, 2) if isinstance(v, float) else v
                          for k, v in rep.items()})
    if args.metrics_json:
        with open(args.metrics_json, "w") as f:
            f.write(eng.registry.snapshot_json())
        print(f"metrics snapshot -> {args.metrics_json}")
    if tracer is not None:
        n = tracer.write_jsonl(args.trace)
        print(f"trace: {n} events -> {args.trace}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
