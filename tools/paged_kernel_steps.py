#!/usr/bin/env python3
"""Device time of the paged decode kernel per live block and per live lane.

    python3 tools/paged_kernel_steps.py [--shape chat,granite]
        [--block-tokens 64,128,256] [--reps 20] [--save FILE.npz]

Runs ``paged_decode_attention`` alone at the decode shapes of the
benchmark's cells: ``chat`` is ``qwen3-1.7b.chat`` (32 lanes, 8 kv heads
of 128, GQA group 2, 16-token pages, 128 pages a lane, scores × 1/√128)
and ``granite`` is ``granite-3-8b-pp2.decode-batch`` (32 lanes × 96
pages, GQA group 4, scores × 1/128).  The pool is bf16 with each lane's
pages scattered at random; idle lanes and the table past a lane's pages
map to page 0, as the engine's block tables do.  Each mix of lane
lengths is traced with the profiler on; the kernel's device time is the
sum of its operation's events (``bench/metrics/paged_attn_roofline.py``'s
``KERNEL``), and the program's that of the program calling it, which
takes the pool flat as the cache holds it (a relayout of the pool shows
there and not in the kernel).  A least-squares fit of every mix's time to
``blocks × t_block + live_lanes × t_lane + t_call`` gives the cost of a
live block of pages, of a live lane's start, and of a call with no live
lane.  ``--block-tokens`` sets the kernel's ``BLOCK_TOKENS`` for each
value in turn, to choose it; the default measures the kernel as it is.
Prints one JSON object.  ``--save`` writes every mix's output, so two
trees' kernels can be compared bit for bit.  Needs a TPU.
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

B, HKV, D, PS = 32, 8, 128, 16
#: name -> (GQA group, pages a lane, score scale)
SHAPES = {"chat": (2, 128, 128 ** -0.5), "granite": (4, 96, 1 / 128)}


def mixes(rng, max_pages: int):
    """Lane lengths of each mix, by name: whole tables, halves,
    quarters, chat's sparse batch (21 lanes of 600–950 tokens), a ragged
    full batch (every lane 384–1536 tokens), each clipped to the table,
    and no live lane."""
    import numpy as np
    full = max_pages * PS
    sparse = np.zeros(B, np.int32)
    sparse[:2 * B // 3] = np.minimum(rng.integers(600, 951, 2 * B // 3), full)
    ragged = np.minimum(rng.integers(384, 1537, B), full).astype(np.int32)
    return {"full": np.full(B, full, np.int32),
            "half": np.full(B, full // 2, np.int32),
            "quarter": np.full(B, full // 4, np.int32),
            "sparse": sparse,
            "ragged": ragged,
            "idle": np.zeros(B, np.int32)}


def block_tables(rng, lens, max_pages: int):
    """Each live lane's pages at random places in the pool; the rest of
    its row, and every idle lane's, is page 0."""
    import numpy as np
    perm = 1 + rng.permutation(B * max_pages)
    bt = np.zeros((B, max_pages), np.int32)
    for b, n in enumerate(lens):
        used = -(-int(n) // PS)
        bt[b, :used] = perm[b * max_pages:b * max_pages + used]
    return bt


def device_seconds(run, reps: int):
    """Mean device time of the kernel, and of the whole program that
    calls it, over ``reps`` calls."""
    import jax
    from bench import trace_reduce
    from bench.metrics.paged_attn_roofline import KERNEL
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(reps):
                out = run()
            out.block_until_ready()
        tr = trace_reduce.load(trace_reduce.find_trace(d))
    chip = tr.chips[min(tr.chips)]
    events = [e for e in chip.ops if re.match(KERNEL, e.name)]
    progs = trace_reduce.modules_holding(chip, KERNEL, -float("inf"),
                                         float("inf"))
    if len(events) != reps or len(progs) != reps:
        raise RuntimeError(f"{len(events)} kernel events, {len(progs)} "
                           f"programs for {reps} calls")
    return tuple(sum(e.end - e.start for e in evs) * 1e-9 / reps
                 for evs in (events, progs))


def measure(shape: str, seed: int, reps: int, outs: dict, tag: str) -> dict:
    """Every mix of one shape under the kernel as it is now, and the
    fit of their times."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import decode_attention as da
    G, max_pages, scale = SHAPES[shape]
    rng = np.random.default_rng(seed)
    n_pages = 1 + B * max_pages
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (B, HKV, G, D), jnp.bfloat16)
    kp = jax.random.normal(keys[1], (n_pages * PS, HKV * D), jnp.bfloat16)
    vp = jax.random.normal(keys[2], (n_pages * PS, HKV * D), jnp.bfloat16)
    fn = jax.jit(lambda q, kp, vp, bt, lens: da.paged_decode_attention(
        q, kp.reshape(n_pages, PS, HKV * D), vp.reshape(n_pages, PS, HKV * D),
        bt, lens, 0, scale=scale, interpret=False))
    ppb = da.pages_per_block(PS, max_pages)
    rows = {}
    for name, lens in mixes(rng, max_pages).items():
        bt = jnp.asarray(block_tables(rng, lens, max_pages))
        lens_d = jnp.asarray(lens)
        outs[f"{tag}.{name}"] = np.asarray(fn(q, kp, vp, bt, lens_d))
        sec, prog = device_seconds(lambda: fn(q, kp, vp, bt, lens_d), reps)
        pages = [-(-int(n) // PS) for n in lens]
        rows[name] = {"live_lanes": sum(p > 0 for p in pages),
                      "live_pages": sum(pages),
                      "blocks": sum(-(-p // ppb) for p in pages),
                      "kernel_us": sec * 1e6, "program_us": prog * 1e6}
    a = np.array([[r["blocks"], r["live_lanes"], 1] for r in rows.values()],
                 float)
    t = np.array([r["kernel_us"] for r in rows.values()])
    (t_block, t_lane, t_call), *_ = np.linalg.lstsq(a, t, rcond=None)
    return {"pages_per_block": ppb, "mixes": rows,
            "t_block_us": float(t_block), "t_lane_us": float(t_lane),
            "t_call_us": float(t_call)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", default="chat,granite")
    ap.add_argument("--block-tokens", default="")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--save", default=None)
    args = ap.parse_args(argv)
    import jax
    import numpy as np
    from repro.kernels import decode_attention as da
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU; JAX finds {dev.platform}", file=sys.stderr)
        return 2
    sweep = [int(t) for t in args.block_tokens.split(",") if t]
    result, outs = {}, {}
    for shape in args.shape.split(","):
        for t in sweep or [da.BLOCK_TOKENS]:
            da.BLOCK_TOKENS = t
            tag = f"{shape}.T{t}"
            result[tag] = measure(shape, args.seed, args.reps, outs, tag)
    if args.save:
        np.savez(args.save, **outs)
    print(json.dumps({"device_kind": dev.device_kind, "reps": args.reps,
                      "shapes": result}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
