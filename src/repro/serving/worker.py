"""Engine-worker subprocess: ``python -m repro.serving.worker``.

One replica of the multi-replica serving stack: a private model +
``AsyncEngine`` + KV page pool behind an :class:`~repro.serving.http.
HttpFrontend`, owned and monitored by ``repro.serving.supervisor`` and
routed to by ``repro.serving.router`` (``launch/serve.py --http
--replicas N``).

Startup handshake: the worker binds (``--port 0`` picks a free port),
then prints one line ``READY port=<N>`` on stdout — the supervisor
blocks on that line before wiring the replica into the router's ring.
Shutdown is SIGTERM/SIGINT -> drain -> exit 0; anything harder
(SIGKILL, the fault-injection tests) is detected upstream as a broken
connection + dead process.

``--arch tiny`` is the subprocess twin of the benchmark suite's
``bench-tiny`` model (same config, same ``PRNGKey(0)`` params), so a
seeded greedy request answered over the wire must be byte-identical to
the in-process engine — the cross-process parity anchor for
``benchmarks/serving_bench.py`` and ``tests/test_router.py``.  Any
registry arch id serves its REDUCED variant, matching
``repro.launch.serve``.
"""

from __future__ import annotations

import argparse
import signal
import sys
import threading


def build_tiny(seed: int = 0):
    """The benchmark suite's ``bench-tiny`` model (see
    ``benchmarks/serving_bench.py``): deterministic params from
    ``PRNGKey(seed)`` so every process derives identical weights."""
    import jax
    import jax.numpy as jnp

    from ..models import ModelConfig, build_model
    cfg = ModelConfig(name="bench-tiny", arch_type="dense", n_layers=4,
                      d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
                      vocab_size=259, dtype=jnp.float32)
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(seed))


def build_serving_model(arch: str, *, full_config: bool = False,
                        seed: int = 0):
    """The model a serving entry point runs, with params from
    ``PRNGKey(seed)``: ``'tiny'`` (:func:`build_tiny`), a registry arch
    at its published widths and dtype (``full_config``), or by default
    its REDUCED float32 variant for CPU hosts."""
    if arch == "tiny":
        return build_tiny(seed)
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ..configs import get_config
    from ..models import build_model, reduced_config
    cfg = get_config(arch)
    if not full_config:
        cfg = reduced_config(cfg)
        cfg = dataclasses.replace(cfg, dtype=jnp.float32,
                                  capacity_factor=4.0,
                                  vocab_size=max(cfg.vocab_size, 259))
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(seed))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="0 picks a free port (reported via READY)")
    ap.add_argument("--arch", default="tiny",
                    help="'tiny' (bench-tiny model) or a registry arch "
                         "id served reduced")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--max-running", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--n-pages", type=int, default=None)
    ap.add_argument("--prefill-chunk", type=int, default=None)
    ap.add_argument("--no-prefix-cache", action="store_true")
    ap.add_argument("--quant", choices=("none", "q4"), default="none",
                    help="weight format (docs/quantization.md)")
    ap.add_argument("--kv-dtype", choices=("fp32", "int8"),
                    default="fp32", help="KV page format")
    ap.add_argument("--spec-decode", type=int, default=0,
                    help="self-speculative decoding lookahead k "
                         "(0 disables; docs/serving.md)")
    ap.add_argument("--token-timeout", type=float, default=120.0)
    args = ap.parse_args(argv)

    from ..launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    model, params = build_serving_model(args.arch, seed=args.seed)

    from ..data.tokenizer import ByteTokenizer
    from . import faults
    from .async_engine import AsyncEngine
    from .http import HttpFrontend

    # fault-injection harness: workers inherit REPRO_FAULTS from the
    # launching shell / supervisor (no-op unless set; docs/robustness.md)
    faults.load_env()
    quant = None
    if args.quant != "none" or args.kv_dtype != "fp32":
        from ..quant.policy import QuantPolicy
        quant = QuantPolicy(weights=args.quant, kv_dtype=args.kv_dtype)
    engine = AsyncEngine(
        model, params, max_len=args.max_len, max_running=args.max_running,
        page_size=args.page_size, n_pages=args.n_pages,
        prefill_chunk=args.prefill_chunk,
        prefix_cache=not args.no_prefix_cache, quant=quant,
        spec_decode=args.spec_decode)
    fe = HttpFrontend(engine, tokenizer=ByteTokenizer(), host=args.host,
                      port=args.port, token_timeout=args.token_timeout)
    fe.start()

    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: stop.set())
    # the supervisor's handshake line — keep the format stable
    print(f"READY port={fe.port}", flush=True)
    stop.wait()
    fe.close(shutdown_backend=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
