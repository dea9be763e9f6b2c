#!/usr/bin/env python3
"""Device time of the paged decode kernel per grid step, live and dead.

    python3 tools/paged_kernel_steps.py [--reps 20] [--save FILE.npz]

Runs ``paged_decode_attention`` alone at the ``qwen3-1.7b.chat`` cell's
decode shapes (32 lanes, 8 kv heads of 128, GQA group 2, 16-token
pages, 128 pages a lane, bf16 pool with pages scattered at random)
under several mixes of lane lengths.  Each mix is traced with the
profiler on; the kernel's device time is the sum of its operation's
events (``bench/metrics/paged_attn_roofline.py``'s ``KERNEL``).  A
grid step is live when its page holds a visible slot of its lane and
dead otherwise; a least-squares fit of every mix's time to
``live × t_live + dead × t_dead`` gives the cost of each kind.  Prints
one JSON object.  ``--save`` writes every mix's output, so two trees'
kernels can be compared bit for bit.  Needs a TPU.
"""

import argparse
import json
import os
import re
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

B, HKV, G, D, PS, MAX_PAGES = 32, 8, 2, 128, 16, 128


def mixes(rng):
    """Lane lengths of each mix, by name."""
    import numpy as np
    full = MAX_PAGES * PS
    chat = np.zeros(B, np.int32)
    chat[:21] = rng.integers(600, 951, 21)
    return {"full": np.full(B, full, np.int32),
            "half": np.full(B, full // 2, np.int32),
            "quarter": np.full(B, full // 4, np.int32),
            "chat": chat,
            "idle": np.zeros(B, np.int32)}


def kernel_seconds(run, reps: int) -> float:
    import jax
    from bench import trace_reduce
    from bench.metrics.paged_attn_roofline import KERNEL
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(reps):
                out = run()
            out.block_until_ready()
        tr = trace_reduce.load(trace_reduce.find_trace(d))
    events = [e for chip in tr.chips.values() for e in chip.ops
              if re.match(KERNEL, e.name)]
    if len(events) != reps:
        raise RuntimeError(f"{len(events)} kernel events for {reps} calls")
    return sum(e.end - e.start for e in events) * 1e-9 / reps


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--save", default=None)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.decode_attention import paged_decode_attention
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU; JAX finds {dev.platform}", file=sys.stderr)
        return 2
    rng = np.random.default_rng(args.seed)
    n_pages = 1 + B * MAX_PAGES
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    q = jax.random.normal(keys[0], (B, HKV, G, D), jnp.bfloat16)
    kp = jax.random.normal(keys[1], (n_pages, PS, HKV, D), jnp.bfloat16)
    vp = jax.random.normal(keys[2], (n_pages, PS, HKV, D), jnp.bfloat16)
    bt = jnp.asarray((1 + rng.permutation(B * MAX_PAGES))
                     .reshape(B, MAX_PAGES), jnp.int32)
    fn = jax.jit(lambda q, kp, vp, bt, lens: paged_decode_attention(
        q, kp, vp, bt, lens, 0, scale=D ** -0.5, interpret=False))

    rows, outs = {}, {}
    for name, lens in mixes(rng).items():
        lens_d = jnp.asarray(lens)
        outs[name] = np.asarray(fn(q, kp, vp, bt, lens_d))   # compile, warm
        sec = kernel_seconds(lambda: fn(q, kp, vp, bt, lens_d), args.reps)
        live = int(sum(-(-int(n) // PS) for n in lens))
        rows[name] = {"live_steps": live,
                      "dead_steps": B * MAX_PAGES - live,
                      "kernel_us": sec * 1e6}
    a = np.array([[r["live_steps"], r["dead_steps"]] for r in rows.values()],
                 float)
    t = np.array([r["kernel_us"] for r in rows.values()])
    (t_live, t_dead), *_ = np.linalg.lstsq(a, t, rcond=None)
    if args.save:
        np.savez(args.save, **outs)
    print(json.dumps({"device_kind": dev.device_kind, "reps": args.reps,
                      "mixes": rows, "t_live_us": float(t_live),
                      "t_dead_us": float(t_dead)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
