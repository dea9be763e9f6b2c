"""Training launcher: ``python -m repro.launch.train --arch <id>``.

Runs a REDUCED variant of the assigned architecture end-to-end on this
host (CPU) with the full production pipeline — data, model zoo, AdamW,
microbatching, checkpointing.  ``--full-config`` switches to the real
config (only sensible on real hardware); ``--devices N`` forces N host
devices for a small data-parallel mesh demo.

Examples:
    python -m repro.launch.train --arch qwen3-1.7b --steps 60
    python -m repro.launch.train --arch mamba2-370m --steps 40 \\
        --seq-len 64 --batch 8
    python -m repro.launch.train --arch phi3.5-moe-42b-a6.6b --steps 30 \\
        --devices 4   # 4-way data-parallel on host devices
"""

import argparse
import dataclasses
import os
import sys


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--full-config", action="store_true",
                    help="use the production config (real hardware only)")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (data-parallel demo)")
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..configs import get_config, list_archs
    from ..data.pipeline import PackedLMDataset, stub_frames, \
        stub_image_embeds
    from ..models import build_model, reduced_config
    from ..training.loop import make_train_step
    from ..training.optimizer import AdamWConfig, adamw_init
    from ..training.checkpoint import save_checkpoint
    from .compile_cache import enable_compile_cache
    from .mesh import make_host_mesh

    if args.arch not in list_archs():
        ap.error(f"unknown arch; choose from {list_archs()}")
    enable_compile_cache()
    cfg = get_config(args.arch)
    if not args.full_config:
        cfg = reduced_config(cfg)
        cfg = dataclasses.replace(cfg, dtype=jnp.float32,
                                  capacity_factor=4.0)
    model = build_model(cfg)
    print(f"arch={cfg.name} params={cfg.param_count() / 1e6:.1f}M "
          f"(reduced={not args.full_config}) devices={len(jax.devices())}")

    params = model.init(jax.random.PRNGKey(0))
    ds = PackedLMDataset(seq_len=args.seq_len, n_docs=2000,
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

    batches = ds.batches(args.batch, extra_fn=extra_fn)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 2),
                          total_steps=args.steps)
    step_fn = make_train_step(model, opt_cfg,
                              microbatches=args.microbatches)

    if args.devices and args.devices > 1:
        mesh = make_host_mesh(model=1, data=args.devices)
        dp = NamedSharding(mesh, P("data"))
        repl = NamedSharding(mesh, P())

        def shard_batch(b):
            return {k: jax.device_put(v, dp) for k, v in b.items()}
        with mesh:
            step_fn = jax.jit(step_fn)
    else:
        shard_batch = lambda b: b  # noqa: E731
        step_fn = jax.jit(step_fn)

    opt_state = adamw_init(params)
    import time
    t0 = time.time()
    first = last = None
    for step in range(args.steps):
        params, opt_state, metrics = step_fn(params, opt_state,
                                             shard_batch(next(batches)))
        loss = float(metrics["loss"])
        first = first if first is not None else loss
        last = loss
        if step % 10 == 0 or step == args.steps - 1:
            print(f"  step {step:4d} loss {loss:.4f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"gnorm {float(metrics['grad_norm']):.2f} "
                  f"({time.time() - t0:.1f}s)")
    print(f"loss {first:.3f} -> {last:.3f}")
    if args.ckpt:
        print("checkpoint:", save_checkpoint(args.ckpt, args.steps,
                                             {"params": params}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
