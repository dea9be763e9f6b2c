"""Multi-device TP semantics (subprocess: forces 8 host devices).

In-process tests must see the single real CPU device, so everything
needing a real multi-device mesh runs in a child interpreter with
``--xla_force_host_platform_device_count=8``.
"""

import os
import subprocess
import sys
import textwrap

import pytest

# every test here spawns a child interpreter with 8 forced host
# devices — minutes of wall time, so the whole module is slow-lane
pytestmark = pytest.mark.slow


def _run(snippet: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(snippet)],
                         capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_tp_blocks_match_reference_on_8_devices():
    print(_run("""
        import jax, numpy as np
        from repro.core import tp
        assert len(jax.devices()) == 8
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("model",))
        rng = np.random.default_rng(0)
        d, f, t = 32, 64, 8
        params = {k: (rng.normal(size=s)*0.1).astype(np.float32)
                  for k, s in [("w_gate",(d,f)),("w_up",(d,f)),
                               ("w_down",(f,d))]}
        x = rng.normal(size=(t, d)).astype(np.float32)
        ref = tp.mlp_reference(params, x)
        for mode in ("sync_a", "sync_b"):
            blk = tp.make_tp_block(mesh, "mlp", sync_mode=mode)
            out = blk(params, x)
            assert np.allclose(out, ref, atol=1e-5), mode
        ap = {k: (rng.normal(size=(d,d))*0.1).astype(np.float32)
              for k in ("w_q","w_k","w_v","w_o")}
        refa = tp.attention_reference(ap, x, n_heads=8)
        for mode in ("sync_a", "sync_b"):
            blk = tp.make_tp_block(mesh, "attention", n_heads=8,
                                   sync_mode=mode)
            assert np.allclose(blk(ap, x), refa, atol=1e-5), mode
        print("TP-OK")
    """))


@pytest.mark.slow
def test_sharded_params_placement():
    print(_run("""
        import jax, numpy as np
        from repro.core import tp
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("model",))
        params = {"w_up": np.zeros((16, 64), np.float32),
                  "w_down": np.zeros((64, 16), np.float32),
                  "norm": np.zeros((16,), np.float32)}
        sharded = tp.shard_params(params, mesh)
        # §3.2: w_up row-partitioned (axis 1), w_down col (axis 0)
        P = jax.sharding.PartitionSpec
        assert sharded["w_up"].sharding.spec == P(None, "model")
        assert sharded["w_down"].sharding.spec == P("model", None)
        assert sharded["norm"].sharding.spec == jax.sharding.PartitionSpec()
        # node-local bytes: each device holds 1/8 of each matrix
        shard_bytes = sharded["w_up"].addressable_shards[0].data.nbytes
        assert shard_bytes == 16*64*4 // 8
        print("SHARD-OK")
    """))


@pytest.mark.slow
def test_seq_sharded_flash_decode_combine():
    """combine_partials under a real sequence-sharded mesh."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np, functools
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.models.attention import (flash_attention,
                                            combine_partials)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        B,S,H,D = 1, 64, 2, 16
        rng = np.random.default_rng(0)
        q = rng.normal(size=(B,1,H,D)).astype(np.float32)
        k = rng.normal(size=(B,S,H,D)).astype(np.float32)
        v = rng.normal(size=(B,S,H,D)).astype(np.float32)
        full = flash_attention(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v), causal=True,
                               q_offset=S-1, chunk=16)
        def body(q_, k_, v_):
            size = k_.shape[1]
            idx = jax.lax.axis_index("data")
            p = flash_attention(q_, k_, v_, causal=True, q_offset=S-1,
                                kv_offset=idx*size, chunk=16,
                                return_partial=True)
            return combine_partials(p, "data", q_.dtype)
        fn = shard_map(body, mesh=mesh,
                       in_specs=(P(), P(None, "data", None, None),
                                 P(None, "data", None, None)),
                       out_specs=P(), check_vma=False)
        out = fn(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        assert np.allclose(np.asarray(out), np.asarray(full),
                           atol=1e-5), np.abs(np.asarray(out)-np.asarray(full)).max()
        print("SEQSHARD-OK")
    """))


@pytest.mark.slow
def test_dryrun_reduced_case_runs():
    """End-to-end dryrun module on one pair (real 512-device lowering)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "gemma3-1b", "--shape", "decode_32k"],
        capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "roofline:" in out.stdout and "1 ok, 0 failed" in out.stdout


@pytest.mark.slow
def test_moe_hook_tp_and_ep_match_dense_oracle():
    """shard_map MoE dispatch (TP-in-expert and expert-parallel) vs
    the dense oracle, on a real 2x4 mesh."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.shardings import Policy, make_moe_hook
        from repro.models.moe import init_moe, moe
        from repro.models.config import ModelConfig
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        d, f, E, k = 16, 32, 8, 2
        cfg = ModelConfig(name="m", arch_type="moe", n_layers=2,
                          d_model=d, n_heads=2, n_kv_heads=1, d_ff=f,
                          vocab_size=64, n_experts=E, experts_per_token=k,
                          capacity_factor=8.0, dtype=jnp.float32)
        params = init_moe(jax.random.PRNGKey(0), d, f, E, "silu",
                          jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 6, d),
                              jnp.float32)
        ref, _ = moe(params, x, k=k, act="silu", impl="dense")
        with mesh:
            for ep in (False, True):
                hook = make_moe_hook(cfg, mesh, Policy(expert_parallel=ep),
                                     batch_size=4)
                y, aux = jax.jit(hook)(params, x)
                err = np.abs(np.asarray(y) - np.asarray(ref)).max()
                assert err < 1e-4, (ep, err)
        print("MOE-HOOK-OK")
    """))
