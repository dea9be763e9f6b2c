"""TPU v5e compile rehearsals of the main-path Pallas kernels.

The chip's compiler is installed with jaxlib and compiles for a chip
that is described, not attached: each case lowers a kernel at real
widths for one v5e device and asserts the program holds a Mosaic
kernel (``tpu_custom_call``).  Interpret-mode oracle tests cannot see
what this catches — block shapes the chip's tiling refuses, integer
ops Mosaic does not legalize.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time, and every
test worker imports every test file.  The persistent compilation cache
is switched off around these compiles (an entry written for a described
chip cannot be read back without one).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# qwen3-1.7b decode: 8 slots, 8 kv heads (2 per chip under 4-way TP),
# GQA group 2, head_dim 128, 16-token pages, max_len 1024; the
# granite-3-8b pipeline stage: 32 lanes x 96 pages (max_len 1536), 32
# query / 8 kv heads, scores times Granite's attention_multiplier 1/128;
# the qwen3-1.7b.chat cell's decode: 32 lanes x 128 pages (max_len 2048)
@pytest.mark.parametrize("B,hkv,G,max_pages,scale", [
    (8, 8, 2, 64, 128 ** -0.5), (8, 2, 2, 64, 128 ** -0.5),
    (32, 8, 4, 96, 1 / 128), (32, 8, 2, 128, 128 ** -0.5)],
    ids=["hkv8", "hkv2_tp_local", "granite_stage", "chat_decode"])
def test_paged_decode_attention_compiles(one_chip, B, hkv, G, max_pages,
                                         scale):
    from repro.kernels.decode_attention import paged_decode_attention
    D, ps = 128, 16
    n_pages = 1 + B * max_pages

    # the pool as the paged cache holds it, one (rows, Hkv * D) buffer,
    # viewed per page as the ops wrapper does
    def fn(q, kp, vp, bt, lens):
        return paged_decode_attention(
            q, kp.reshape(n_pages, ps, hkv * D),
            vp.reshape(n_pages, ps, hkv * D), bt, lens, 0, scale=scale,
            interpret=False)

    text = _compiled_text(
        fn, one_chip,
        ((B, hkv, G, D), jnp.bfloat16),
        ((n_pages * ps, hkv * D), jnp.bfloat16),
        ((n_pages * ps, hkv * D), jnp.bfloat16),
        ((B, max_pages), jnp.int32),
        ((B,), jnp.int32))
    assert "tpu_custom_call" in text
    assert _pool_copies(text, n_pages * ps * hkv * D) == []


@pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
def test_paged_decode_step_copies_no_pool(one_chip, monkeypatch, kv_dtype):
    """A whole decode step over the paged cache at qwen3-1.7b's attention
    widths (8 kv heads of 128, 16-token pages, 32 lanes × 128 pages):
    the Pallas kernel and the int8 pages' gather read each layer's pool
    buffer where it lies, so the program holds no pool-sized array but
    the buffers themselves and their in-place row writes."""
    import dataclasses
    import math
    import repro.kernels.ops as ops
    from repro.configs.qwen3_1p7b import CONFIG
    from repro.models import build_model
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(CONFIG, n_layers=1, d_model=256, d_ff=512,
                              vocab_size=1024)
    model = build_model(cfg)
    B, max_len, ps = 32, 2048, 16
    sds = lambda t: jax.tree.map(      # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(
        B, max_len, page_size=ps, kv_dtype=kv_dtype))
    step = jax.jit(lambda p, c, t, pos: model.decode_step(
        p, c, t, pos, page_size=ps), donate_argnums=1)
    text = step.lower(sds(params), sds(cache),
                      sds(jax.ShapeDtypeStruct((B, 1), jnp.int32)),
                      sds(jax.ShapeDtypeStruct((B,), jnp.int32))
                      ).compile().as_text()
    pool = cache["layers"][0]["self"]["k"]
    assert _pool_copies(text, math.prod(pool.shape)) == []
    if kv_dtype == "fp32":
        assert "tpu_custom_call" in text


def _pool_copies(text: str, pool_elems: int):
    """Instructions of a compiled program that produce an array of the
    page pool's size other than by a parameter, a bitcast or the
    in-place write of the step's rows (a scatter, or the fusion XLA
    makes of one): a relayout of the pool (a reshape to another tiling,
    a copy), which on a TPU moves every byte of it on every call."""
    import math
    import re
    out = []
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* (\S+?)\(", ln)
        if (m and m.group(2) not in ("parameter", "bitcast", "scatter")
                and not (m.group(2) == "fusion"
                         and re.search(r'op_name="[^"]*/scatter"', ln))
                and math.prod(int(d) for d in m.group(1).split(",") if d)
                == pool_elems):
            out.append(ln.strip()[:120])
    return out


def _readers_kernel_patterns():
    """The ``KERNEL`` pattern of each benchmark reader that finds the
    paged kernel in a device trace by its instruction name, read from
    the reader's source (importing it would import the benchmark)."""
    import ast
    from pathlib import Path
    metrics = Path(__file__).resolve().parents[1] / "bench" / "metrics"
    out = {}
    for name in ("decode_step_ms", "paged_attn_roofline",
                 "prefill_ms_per_ktok", "decode_hbm_roofline"):
        src = (metrics / f"{name}.py").read_text().splitlines()
        line = next(ln for ln in src if ln.startswith("KERNEL = "))
        out[name] = ast.literal_eval(line.split("=", 1)[1].strip())
    return out


# the paged kernel's instruction must keep the name the trace readers
# match, called directly, through another jitted wrapper, and inside a
# named scope as the runner's decode program calls it
@pytest.mark.parametrize("via", ["direct", "wrapper", "named_scope"])
def test_paged_kernel_instruction_name(one_chip, via):
    import re

    from repro.kernels.decode_attention import paged_decode_attention
    B, G, D, ps, max_pages, hkv = 8, 2, 128, 16, 64, 8
    n_pages = 1 + B * max_pages

    def kernel(q, kp, vp, bt, lens):
        return paged_decode_attention(
            q, kp.reshape(n_pages, ps, hkv * D),
            vp.reshape(n_pages, ps, hkv * D), bt, lens, 0,
            scale=D ** -0.5, interpret=False)

    wrapped = jax.jit(kernel)

    def fn(q, kp, vp, bt, lens):
        # scaled, so the kernel is not the program's ROOT instruction,
        # as it is not in the decode program
        if via == "direct":
            return kernel(q, kp, vp, bt, lens) * 2.0
        if via == "wrapper":
            return wrapped(q, kp, vp, bt, lens) * 2.0
        with jax.named_scope("serving.decode"):
            return wrapped(q, kp, vp, bt, lens) * 2.0

    text = _compiled_text(
        fn, one_chip,
        ((B, hkv, G, D), jnp.bfloat16),
        ((n_pages * ps, hkv * D), jnp.bfloat16),
        ((n_pages * ps, hkv * D), jnp.bfloat16),
        ((B, max_pages), jnp.int32),
        ((B,), jnp.int32))
    lines = [ln.strip() for ln in text.splitlines()]
    for name, pattern in _readers_kernel_patterns().items():
        rx = re.compile(pattern)
        assert sum(1 for ln in lines if rx.search(ln)) == 1, name


@pytest.mark.parametrize("m", [1, 8])
def test_q4_gemm_compiles(one_chip, m):
    from repro.kernels.q4_gemm import q4_gemm
    from repro.quant.q4_0 import BLOCK
    K, N = 2048, 6144

    def fn(x, packed, scales):
        return q4_gemm(x, packed, scales, interpret=False)

    text = _compiled_text(fn, one_chip,
                          ((m, K), jnp.float32),
                          ((K // 2, N), jnp.uint8),
                          ((K // BLOCK, N), jnp.float32))
    assert "tpu_custom_call" in text


def test_rglru_scan_compiles(one_chip):
    from repro.kernels.rglru_scan import rglru_scan_kernel
    B, T, W = 2, 256, 2560

    def fn(a, u):
        return rglru_scan_kernel(a, u, interpret=False)

    text = _compiled_text(fn, one_chip, ((B, T, W), jnp.float32),
                          ((B, T, W), jnp.float32))
    assert "tpu_custom_call" in text


def test_fixture_left_backend_on_cpu(one_chip):
    """Describing the chip must not move this process off the CPU: the
    rest of the suite computes on the CPU backend."""
    assert jax.default_backend() == "cpu"
    assert np.asarray(jnp.ones(2) + 1).tolist() == [2.0, 2.0]
