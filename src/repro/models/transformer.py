"""Unified transformer covering all assigned architecture families.

``build_model(cfg)`` returns a :class:`Model` exposing

    init(key)                      -> params
    forward(params, batch)         -> logits              (teacher forcing)
    loss(params, batch)            -> (scalar, metrics)
    init_cache(B, max_len, ...)    -> cache (zeros)
    prefill(params, batch, cache)  -> (logits, cache)
    decode_step(params, cache, tokens, pos) -> (logits, cache)

Layer stacks compile as a single ``lax.scan`` over stacked parameters
when every layer has the same structure (uniform mode — all dense
archs, MoE archs, Mamba-2 and Whisper), and as an unrolled loop for
heterogeneous patterns (RecurrentGemma's (R,R,A), Llama-3.2-Vision's
every-5th cross-attention layer).  Attention *metadata* — per-layer
sliding window and RoPE base — stays data, so gemma3's 5:1
local:global pattern remains uniform.

KV caches are ring buffers: slot = position mod cache_len, with an
explicit per-slot absolute-position array used for masking.  With
``cache_len == max_len`` this degenerates to the ordinary linear cache;
with ``cache_len == window`` it is the sliding-window cache used for
the long_500k shapes (DESIGN.md §4).

``init_cache(page_size=...)`` instead builds the **paged** cache for
the continuous-batching engine: a shared physical page pool addressed
through per-slot block tables, with ``prefill_paged`` /
vector-position ``decode_step`` as the compiled entry points (see
``init_cache`` and ``repro.serving.kv_pool`` for the layout).  The
paged pool is held as **per-layer buffers run through an unrolled
layer loop** (``_run_paged_layers``), never through the layer scan's
carry — the scan would copy the whole pool every compiled step.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from jax.ad_checkpoint import checkpoint_name

from .attention import flash_attention
from .common import (Params, dense_init, embed_init, layer_norm, mlp, init_mlp,
                     proj, rms_norm, unembed)
from .config import ModelConfig
from .moe import init_moe, moe
from .recurrent import RGLRUState, init_rglru_block, rglru_block
from .ssm import SSDState, init_ssd, ssd_block


# ----------------------------------------------------------------------
# parameter init
# ----------------------------------------------------------------------

def _init_norm(cfg: ModelConfig, d: int) -> Params:
    p: Params = {"g": jnp.zeros((d,), cfg.dtype)}
    if cfg.arch_type == "audio":  # whisper uses LayerNorm with bias
        p = {"g": jnp.ones((d,), cfg.dtype), "b": jnp.zeros((d,), cfg.dtype)}
    return p


def _apply_norm(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    if "b" in p:
        return layer_norm(x, p["g"], p["b"], cfg.norm_eps)
    return rms_norm(x, p["g"], cfg.norm_eps)


def _init_attn(key: jax.Array, cfg: ModelConfig, *, cross: bool) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    qdim, kvdim = cfg.n_heads * hd, cfg.n_kv_heads * hd
    ks = jax.random.split(key, 6)
    p: Params = {
        "w_q": dense_init(ks[0], d, qdim, cfg.dtype),
        "w_k": dense_init(ks[1], d, kvdim, cfg.dtype),
        "w_v": dense_init(ks[2], d, kvdim, cfg.dtype),
        "w_o": dense_init(ks[3], qdim, d, cfg.dtype),
    }
    if cfg.qkv_bias:
        p["b_q"] = jnp.zeros((qdim,), cfg.dtype)
        p["b_k"] = jnp.zeros((kvdim,), cfg.dtype)
        p["b_v"] = jnp.zeros((kvdim,), cfg.dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), cfg.dtype)
        p["k_norm"] = jnp.zeros((hd,), cfg.dtype)
    if cross:
        p["gate"] = jnp.zeros((), cfg.dtype)   # tanh-gated cross-attn
    return p


def _init_ffn(key: jax.Array, cfg: ModelConfig) -> Params:
    if cfg.n_experts:
        return {"moe": init_moe(key, cfg.d_model, cfg.d_ff, cfg.n_experts,
                                cfg.act, cfg.dtype)}
    return {"mlp": init_mlp(key, cfg.d_model, cfg.d_ff, cfg.act, cfg.dtype)}


def _init_layer(key: jax.Array, cfg: ModelConfig, kind: str, *,
                decoder_cross: bool = False) -> Params:
    ks = jax.random.split(key, 5)
    p: Params = {"ln1": _init_norm(cfg, cfg.d_model)}
    if kind == "attn":
        p["attn"] = _init_attn(ks[0], cfg, cross=False)
        if decoder_cross:  # whisper decoder: self + cross in every layer
            p["ln_x"] = _init_norm(cfg, cfg.d_model)
            p["xattn"] = _init_attn(ks[1], cfg, cross=True)
        p["ln2"] = _init_norm(cfg, cfg.d_model)
        p.update(_init_ffn(ks[2], cfg))
    elif kind == "xattn":
        p["xattn"] = _init_attn(ks[0], cfg, cross=True)
        p["ln2"] = _init_norm(cfg, cfg.d_model)
        p.update({"mlp": init_mlp(ks[2], cfg.d_model, cfg.d_ff, cfg.act,
                                  cfg.dtype)})
    elif kind == "rglru":
        p["rglru"] = init_rglru_block(ks[0], cfg.d_model,
                                      cfg.lru_width or cfg.d_model,
                                      cfg.ssm_conv, cfg.dtype)
        p["ln2"] = _init_norm(cfg, cfg.d_model)
        p.update(_init_ffn(ks[2], cfg))
    elif kind == "ssd":
        p["ssd"] = init_ssd(ks[0], cfg.d_model, n_heads=cfg.ssm_heads,
                            head_dim=cfg.ssm_head_dim, d_state=cfg.ssm_state,
                            n_groups=cfg.ssm_groups, conv_width=cfg.ssm_conv,
                            dtype=cfg.dtype)
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    return p


def _times(x: jax.Array, m: float) -> jax.Array:
    """``x * m`` computed in float32 and kept in ``x``'s dtype; ``m`` of 1
    returns ``x`` itself, so a plain block's program is unchanged."""
    if m == 1.0:
        return x
    return (x.astype(jnp.float32) * m).astype(x.dtype)


# ----------------------------------------------------------------------
# attention forward (shared by self/cross, train/prefill/decode)
# ----------------------------------------------------------------------

def _project_qkv(cfg: ModelConfig, ap: Params, xq: jax.Array,
                 xkv: jax.Array, qmm=None,
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    hd = cfg.resolved_head_dim
    q = proj(xq, ap["w_q"], qmm)
    k = proj(xkv, ap["w_k"], qmm)
    v = proj(xkv, ap["w_v"], qmm)
    if cfg.qkv_bias:
        q, k, v = q + ap["b_q"], k + ap["b_k"], v + ap["b_v"]
    Bq, Sq = xq.shape[:2]
    Bk, Sk = xkv.shape[:2]
    q = q.reshape(Bq, Sq, cfg.n_heads, hd)
    k = k.reshape(Bk, Sk, cfg.n_kv_heads, hd)
    v = v.reshape(Bk, Sk, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, ap["q_norm"], cfg.norm_eps)
        k = rms_norm(k, ap["k_norm"], cfg.norm_eps)
    return q, k, v


def _rope(cfg: ModelConfig, x: jax.Array, positions: jax.Array,
          theta: jax.Array) -> jax.Array:
    d = x.shape[-1]
    exponents = jnp.arange(0, d, 2, dtype=jnp.float32) / d
    freqs = jnp.power(jnp.asarray(theta, jnp.float32), -exponents)
    angles = positions[..., None].astype(jnp.float32) * freqs
    sin = jnp.sin(angles)[..., None, :]
    cos = jnp.cos(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def _write_cache(cache_k: jax.Array, cache_v: jax.Array,
                 cache_pos: jax.Array, k: jax.Array, v: jax.Array,
                 positions: jax.Array):
    """Ring-buffer write. cache_* (B,M,H,D), positions (S,) absolute."""
    M = cache_k.shape[1]
    S = k.shape[1]
    if S >= M:  # keep only the last M tokens (static shapes)
        k, v = k[:, -M:], v[:, -M:]
        positions = positions[-M:]
    slots = positions % M
    if k.shape[1] == 1:
        # single-token decode: dynamic_update_slice keeps a sharded
        # sequence axis local (a scatter would make GSPMD gather it)
        slot = slots[0]
        cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k, slot, 1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v, slot, 1)
        cache_pos = jax.lax.dynamic_update_slice_in_dim(
            cache_pos, positions, slot, 0)
    else:
        cache_k = cache_k.at[:, slots].set(k)
        cache_v = cache_v.at[:, slots].set(v)
        cache_pos = cache_pos.at[slots].set(positions)
    return cache_k, cache_v, cache_pos


# ----------------------------------------------------------------------
# layer forward
# ----------------------------------------------------------------------

ATTN_CHUNK = 512


def _remat_policy(cfg: ModelConfig):
    if cfg.remat_save_gather:
        return jax.checkpoint_policies.save_only_these_names("block_out")
    return jax.checkpoint_policies.nothing_saveable


def _paged_attn(cfg: ModelConfig, q: jax.Array, k: jax.Array, v: jax.Array,
                window: jax.Array, cache: Dict[str, jax.Array],
                paged: Dict[str, Any],
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Slot-mapped cache write + block-table attention read.

    ``cache['k']/['v']`` are THIS layer's flat page-pool buffers
    ((n_pages * page_size, Hkv, D)); ``paged`` carries the per-call slot
    mapping (see ``Model.init_cache`` docstring).  Prefill (S > 1)
    scatters the fresh K/V rows to their physical slots; a one-shot
    prefill of a fresh sequence attends over the fresh K/V directly
    (the cache was empty, identical maths), while a *resumed* prefill
    chunk (``paged['prefill_ctx']`` present — chunked prefill or a
    prefix-cached prompt) gathers the full context through the block
    table and attends with absolute-position causal masking.  Decode
    (S == 1) scatters one row per sequence and attends through the
    block table with the gather-based paged kernel.

    An **int8 page pool** (``init_cache(kv_dtype="int8")``) is detected
    by its ``k_scale``/``v_scale`` buffers: fresh K/V rows are
    quantized per (token, kv head) before the scatter (codes + scale
    land in the same physical slots), and reads dequantize *after* the
    gather — the resumed-prefill context gather here, the block-table
    gather inside the paged decode read — so the pool is never
    dequantized wholesale (``repro.quant.kv_int8``).  The fresh
    one-shot prefill still attends over the exact fp32 K/V (the maths
    needs nothing from the pool); later reads see the quantized rows.
    """
    from ..kernels.ops import paged_gqa_decode_attention
    from ..quant.kv_int8 import dequantize_rows, quantize_rows
    B, S = q.shape[:2]
    ps = paged["page_size"]
    write_slots = paged["write_slots"]
    quantized = "k_scale" in cache
    new_cache: Dict[str, jax.Array] = {}

    def write(rows_k, rows_v):
        """Scatter this call's fresh rows ((n, Hkv, D) at write_slots)."""
        if not quantized:
            new_cache["k"] = cache["k"].at[write_slots].set(rows_k)
            new_cache["v"] = cache["v"].at[write_slots].set(rows_v)
            return
        qk_, sk_ = quantize_rows(rows_k)
        qv_, sv_ = quantize_rows(rows_v)
        new_cache["k"] = cache["k"].at[write_slots].set(qk_)
        new_cache["v"] = cache["v"].at[write_slots].set(qv_)
        new_cache["k_scale"] = cache["k_scale"].at[write_slots].set(sk_)
        new_cache["v_scale"] = cache["v_scale"].at[write_slots].set(sv_)

    if "verify" in paged:                # speculative multi-token verify
        # Scatter ALL B*S fresh rows first (draft rows included) — the
        # caller's write_slots already routes idle lanes and past-draft
        # columns to the scratch page — then score each draft offset
        # with the SAME decode kernel a sequential step would run:
        # offset s attends with kv_len + s, exactly the rows visible to
        # a non-speculative decode at that position, so the per-position
        # logits (and hence greedy acceptance) are bitwise-identical to
        # plain decode.  Window masking, softcap and int8 page dequant
        # all ride through the kernel unchanged.
        Hkv, D = k.shape[2], k.shape[3]
        write(k.reshape(B * S, Hkv, D), v.reshape(B * S, Hkv, D))
        outs = []
        for s in range(S):
            kv_len_s = jnp.where(paged["kv_len"] > 0,
                                 paged["kv_len"] + s, 0)
            outs.append(paged_gqa_decode_attention(
                q[:, s:s + 1], new_cache["k"], new_cache["v"],
                paged["block_tables"], kv_len_s, window, page_size=ps,
                softcap=cfg.attn_logit_softcap,
                scale=cfg.attention_multiplier,
                k_scale=new_cache.get("k_scale"),
                v_scale=new_cache.get("v_scale")))
        out = jnp.concatenate(outs, axis=1)
    elif S > 1:                               # prefill: one sequence
        write(k[0], v[0])
        ck, cv = new_cache["k"], new_cache["v"]
        ctx = paged.get("prefill_ctx")
        if ctx is not None:
            # resumed chunk: earlier tokens' K/V are already resident in
            # the pool (written by prior chunks, shared prefix pages, or
            # a copy-on-write clone) — gather them *after* this chunk's
            # write so q sees [0, kv_len) at absolute positions
            if quantized:
                kctx = dequantize_rows(ck[ctx["phys"]],
                                       new_cache["k_scale"][ctx["phys"]],
                                       q.dtype)[None]
                vctx = dequantize_rows(cv[ctx["phys"]],
                                       new_cache["v_scale"][ctx["phys"]],
                                       q.dtype)[None]
            else:
                kctx = ck[ctx["phys"]][None]
                vctx = cv[ctx["phys"]][None]
            out = flash_attention(q, kctx, vctx, causal=True,
                                  window=window, q_offset=ctx["q_offset"],
                                  kv_len=ctx["kv_len"], chunk=ATTN_CHUNK,
                                  softcap=cfg.attn_logit_softcap,
                                  scale=cfg.attention_multiplier)
        else:
            out = flash_attention(q, k, v, causal=True, window=window,
                                  chunk=ATTN_CHUNK,
                                  softcap=cfg.attn_logit_softcap,
                                  scale=cfg.attention_multiplier)
    else:                                     # decode: one token per slot
        write(k[:, 0], v[:, 0])
        out = paged_gqa_decode_attention(
            q, new_cache["k"], new_cache["v"], paged["block_tables"],
            paged["kv_len"], window, page_size=ps,
            softcap=cfg.attn_logit_softcap,
            scale=cfg.attention_multiplier,
            k_scale=new_cache.get("k_scale"),
            v_scale=new_cache.get("v_scale"))
    return out, new_cache


def _self_attn(cfg: ModelConfig, ap: Params, x: jax.Array,
               positions: jax.Array, theta: jax.Array, window: jax.Array,
               cache: Optional[Dict[str, jax.Array]], *, causal: bool,
               decode_hook=None, act_constraint=None, paged=None, qmm=None,
               ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    B, S, d = x.shape
    q, k, v = _project_qkv(cfg, ap, x, x, qmm)
    if act_constraint is not None:
        # batch-only pinning stops GSPMD from "helpfully" splitting the
        # replicated-head attention contraction over the model axis and
        # psum-ing every score chunk (measured: 893 GB/step on gemma3
        # prefill_32k — EXPERIMENTS §Perf)
        q, k, v = act_constraint(q), act_constraint(k), act_constraint(v)
    q = _rope(cfg, q, positions, theta)
    k = _rope(cfg, k, positions, theta)
    new_cache = None
    if cache is not None and paged is not None:
        out, new_cache = _paged_attn(cfg, q, k, v, window, cache, paged)
        merge = paged.get("head_merge")
        if merge is not None:
            # head-sharded TP serving (launch.shardings.make_paged_head
            # _merge): ``out`` holds this shard's local query heads —
            # merge to the full head set (one psum, the layer's only
            # collective) so the replicated w_o below sees the same
            # operand as the single-shard engine, bit for bit
            out = merge(out)
    elif cache is not None and decode_hook is not None and S == 1:
        # sequence-sharded flash-decoding with local cache write
        # (launcher-installed; see launch.shardings.make_decode_attn_hook)
        out, ck, cv, cp = decode_hook(q, k, v, cache["k"], cache["v"],
                                      cache["pos"], window, positions[0])
        new_cache = {"k": ck, "v": cv, "pos": cp}
    elif cache is not None:
        ck, cv, cp = _write_cache(cache["k"], cache["v"], cache["pos"],
                                  k, v, positions)
        new_cache = {"k": ck, "v": cv, "pos": cp}
        if S > 1:
            # prefill: the cache was empty, so attending over the fresh
            # (batch-sharded, model-replicated) k/v is identical maths
            # and independent of the cache's storage sharding
            out = flash_attention(
                q, k, v, causal=True, window=window, chunk=ATTN_CHUNK,
                softcap=cfg.attn_logit_softcap,
                scale=cfg.attention_multiplier)
        else:
            out = flash_attention(
                q, ck, cv, causal=True, window=window,
                q_offset=positions[0], kv_positions=cp, chunk=ATTN_CHUNK,
                softcap=cfg.attn_logit_softcap,
                scale=cfg.attention_multiplier)
    else:
        # training path: rematerialise the blockwise attention in the
        # backward pass — the kv-chunk scan would otherwise save its
        # (out, m, l) carries for every chunk (≈ S/chunk copies of the
        # output; measured 8.6 GB/layer on llama-vision train_4k)
        def attn_fn(q_, k_, v_, w_):
            return flash_attention(
                q_, k_, v_, causal=causal, window=w_, chunk=ATTN_CHUNK,
                softcap=cfg.attn_logit_softcap,
                scale=cfg.attention_multiplier)
        out = jax.checkpoint(attn_fn)(q, k, v, window)
    if act_constraint is not None:
        out = act_constraint(out)
    return proj(out.reshape(B, S, -1), ap["w_o"], qmm), new_cache


def _cross_attn(cfg: ModelConfig, ap: Params, x: jax.Array,
                memory: jax.Array) -> jax.Array:
    """Cross-attention over memory embeddings (B, M, d_model).

    K/V are projected on the fly (their cost is negligible next to the
    self-attention cache traffic; caching them is a recorded perf
    candidate in EXPERIMENTS.md §Perf)."""
    B, S, d = x.shape
    hd = cfg.resolved_head_dim
    M = memory.shape[1]
    q = x @ ap["w_q"]
    k = memory @ ap["w_k"]
    v = memory @ ap["w_v"]
    if cfg.qkv_bias:
        q, k, v = q + ap["b_q"], k + ap["b_k"], v + ap["b_v"]
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, M, cfg.n_kv_heads, hd)
    v = v.reshape(B, M, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, ap["q_norm"], cfg.norm_eps)
        k = rms_norm(k, ap["k_norm"], cfg.norm_eps)
    out = flash_attention(q, k, v, causal=False, chunk=ATTN_CHUNK,
                          scale=cfg.attention_multiplier)
    out = out.reshape(B, S, -1) @ ap["w_o"]
    if "gate" in ap:
        out = jnp.tanh(ap["gate"].astype(jnp.float32)).astype(out.dtype) * out
    return out


def _ffn(cfg: ModelConfig, lp: Params, x: jax.Array,
         moe_hook=None, qmm=None) -> Tuple[jax.Array, jax.Array]:
    if "moe" in lp:
        if moe_hook is not None:   # launcher-installed shard_map dispatch
            return moe_hook(lp["moe"], x)
        y, aux = moe(lp["moe"], x, k=cfg.experts_per_token, act=cfg.act,
                     impl=cfg.moe_impl, capacity_factor=cfg.capacity_factor)
        return y, aux
    return mlp(lp["mlp"], x, cfg.act, qmm), jnp.zeros((), jnp.float32)


def _layer_forward(cfg: ModelConfig, kind: str, lp: Params, x: jax.Array,
                   positions: jax.Array, theta: jax.Array,
                   window: jax.Array, cache: Optional[Dict[str, Any]],
                   memory: Optional[Dict[str, jax.Array]], *,
                   causal: bool, decoder_cross: bool = False,
                   single_step: bool = False, moe_hook=None,
                   decode_hook=None, act_constraint=None, paged=None,
                   qmm=None,
                   ) -> Tuple[jax.Array, Optional[Dict[str, Any]], jax.Array]:
    """One block. Returns (x_out, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache: Optional[Dict[str, Any]] = None
    h = _apply_norm(cfg, lp["ln1"], x)
    if kind == "attn":
        a, kv = _self_attn(cfg, lp["attn"], h, positions, theta, window,
                           None if cache is None else cache.get("self"),
                           causal=causal, decode_hook=decode_hook,
                           act_constraint=act_constraint, paged=paged,
                           qmm=qmm)
        # post-Gather activations are remat save-points: recomputing
        # them would repeat the TP psum in the backward pass
        x = x + _times(checkpoint_name(a, "block_out"),
                       cfg.residual_multiplier)
        new_cache = {} if cache is not None else None
        if kv is not None:
            assert new_cache is not None
            new_cache["self"] = kv
        if decoder_cross:
            hx = _apply_norm(cfg, lp["ln_x"], x)
            assert memory is not None
            x = x + _cross_attn(cfg, lp["xattn"], hx, memory)
        h2 = _apply_norm(cfg, lp["ln2"], x)
        f, aux = _ffn(cfg, lp, h2, moe_hook, qmm)
        x = x + _times(checkpoint_name(f, "block_out"),
                       cfg.residual_multiplier)
    elif kind == "xattn":
        assert memory is not None
        x = x + _cross_attn(cfg, lp["xattn"], h, memory)
        h2 = _apply_norm(cfg, lp["ln2"], x)
        x = x + mlp(lp["mlp"], h2, cfg.act)
        new_cache = {} if cache is not None else None
    elif kind == "rglru":
        st = None if cache is None else RGLRUState(**cache["rglru"])
        y, new_st = rglru_block(lp["rglru"], h, state=st,
                                single_step=single_step)
        x = x + y
        h2 = _apply_norm(cfg, lp["ln2"], x)
        f, aux = _ffn(cfg, lp, h2, moe_hook)
        x = x + f
        if cache is not None:
            new_cache = {"rglru": new_st._asdict()}
    elif kind == "ssd":
        st = None if cache is None else SSDState(**cache["ssd"])
        y, new_st = ssd_block(lp["ssd"], h, n_heads=cfg.ssm_heads,
                              head_dim=cfg.ssm_head_dim,
                              d_state=cfg.ssm_state, n_groups=cfg.ssm_groups,
                              chunk=cfg.ssm_chunk, state=st)
        x = x + y
        if cache is not None:
            new_cache = {"ssd": new_st._asdict()}
    else:
        raise ValueError(kind)
    return x, new_cache, aux


# ----------------------------------------------------------------------
# the Model
# ----------------------------------------------------------------------

class Model:
    """Unified model over a ModelConfig (see module docstring)."""

    def __init__(self, cfg: ModelConfig) -> None:
        self.cfg = cfg
        self.kinds = cfg.layer_kinds
        self.uniform = cfg.uniform
        self.decoder_cross = cfg.is_encoder_decoder
        # periodic pattern archs scan over super-blocks (period p):
        # llama-vision (4 attn + 1 xattn), recurrentgemma (R,R,A) —
        # real per-block remat + O(p) HLO instead of O(n_layers)
        self.block_period = 0
        if not self.uniform:
            p = (len(cfg.block_pattern) if cfg.block_pattern
                 else cfg.cross_attn_every)
            if p and cfg.n_layers >= 2 * p:
                self.block_period = p
        self.n_full_blocks = (cfg.n_layers // self.block_period
                              if self.block_period else 0)
        self.n_tail = (cfg.n_layers - self.n_full_blocks * self.block_period
                       if self.block_period else cfg.n_layers)
        if cfg.residual_multiplier != 1.0 and set(self.kinds) != {"attn"}:
            raise NotImplementedError(
                f"arch {cfg.name!r}: residual_multiplier scales the "
                "branches of self-attention blocks only")
        #: optional sharding hooks installed by the launcher
        #: (repro.launch.shardings): per-layer weight unshard constraint
        #: (FSDP) and activation batch constraint.
        self.param_constraint = None
        self.act_constraint = None
        self.moe_hook = None
        self.decode_attn_hook = None
        self.cache_constraint = None
        self.attn_act_constraint = None   # pin q/k/v only for
                                          # replicated-attention archs
        #: TP serving hook: merges a shard's local attention-head
        #: outputs back to the full head set inside the paged path
        #: (installed by serving.runner in mesh mode; the model itself
        #: is then a per-shard "local" model with divided head counts)
        self.paged_head_merge = None
        #: quantized-matmul hook for the paged serving path (installed
        #: by serving.runner under ``QuantPolicy(weights="q4")`` —
        #: ``repro.quant.policy.make_qmm``).  Dense params pass through
        #: it untouched (plain ``x @ w``), so it is safe to leave
        #: installed; None keeps the hook-free matmul everywhere else.
        self.qmm = None

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------
    def init(self, key: jax.Array) -> Params:
        cfg = self.cfg
        ks = jax.random.split(key, 6)
        params: Params = {
            "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model,
                                cfg.dtype),
            "final_norm": _init_norm(cfg, cfg.d_model),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(ks[1], cfg.d_model,
                                           cfg.vocab_size, cfg.dtype)
        if self.uniform:
            keys = jax.random.split(ks[2], cfg.n_layers)
            params["layers"] = jax.vmap(
                lambda k: _init_layer(k, cfg, self.kinds[0],
                                      decoder_cross=self.decoder_cross)
            )(keys)
        elif self.block_period:
            p_ = self.block_period
            nb = self.n_full_blocks
            keys = jax.random.split(ks[2], cfg.n_layers)
            blocks = []
            for j in range(p_):
                kind = self.kinds[j]
                pos_keys = jnp.stack([keys[b * p_ + j] for b in range(nb)])
                blocks.append(jax.vmap(
                    lambda k, kind=kind: _init_layer(k, cfg, kind)
                )(pos_keys))
            tail = [_init_layer(keys[nb * p_ + t], cfg,
                                self.kinds[nb * p_ + t])
                    for t in range(self.n_tail)]
            params["layers"] = {"blocks": blocks, "tail": tail}
        else:
            keys = jax.random.split(ks[2], cfg.n_layers)
            params["layers"] = [
                _init_layer(keys[i], cfg, kind)
                for i, kind in enumerate(self.kinds)]
        if cfg.is_encoder_decoder:
            ekeys = jax.random.split(ks[3], cfg.n_encoder_layers)
            enc_cfg = dataclasses.replace(cfg, n_experts=0)
            params["encoder"] = {
                "layers": jax.vmap(
                    lambda k: _init_layer(k, enc_cfg, "attn"))(ekeys),
                "final_norm": _init_norm(cfg, cfg.d_model),
            }
        return params

    # ------------------------------------------------------------------
    # caches
    # ------------------------------------------------------------------
    def _layer_cache(self, kind: str, batch: int, cache_len: int,
                     dtype: Any) -> Dict[str, Any]:
        cfg = self.cfg
        hd = cfg.resolved_head_dim
        if kind in ("attn",):
            return {"self": {
                "k": jnp.zeros((batch, cache_len, cfg.n_kv_heads, hd), dtype),
                "v": jnp.zeros((batch, cache_len, cfg.n_kv_heads, hd), dtype),
                "pos": jnp.full((cache_len,), -1, jnp.int32)}}
        if kind == "xattn":
            return {}
        if kind == "rglru":
            width = cfg.lru_width or cfg.d_model
            return {"rglru": {
                "h": jnp.zeros((batch, width), jnp.float32),
                "conv": jnp.zeros((batch, cfg.ssm_conv - 1, width), dtype)}}
        if kind == "ssd":
            d_inner = cfg.ssm_heads * cfg.ssm_head_dim
            conv_ch = d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
            return {"ssd": {
                "state": jnp.zeros((batch, cfg.ssm_heads, cfg.ssm_head_dim,
                                    cfg.ssm_state), jnp.float32),
                "conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_ch),
                                  dtype)}}
        raise ValueError(kind)

    def init_cache(self, batch: int, max_len: int, *,
                   cache_len: Optional[int] = None,
                   memory_len: int = 0,
                   page_size: Optional[int] = None,
                   n_pages: Optional[int] = None,
                   kv_dtype: str = "fp32") -> Dict[str, Any]:
        """Zero cache.  ``cache_len`` < max_len -> sliding ring buffer.

        ``page_size`` switches to the **paged slot/block-table view**
        used by the continuous-batching engine: instead of one dense
        (batch, cache_len) ring per sequence, all sequences share one
        physical pool of ``n_pages`` fixed-size pages per layer and are
        addressed through it —

        * ``layers[i].self.k/v``  (n_pages * page_size, Hkv, D) flat
          page-pool buffer of layer ``i`` (page 0 is reserved scratch:
          idle batch slots and padded prefill positions write there).
          The layers are a **Python list of independent buffers**, not
          one stacked (L, ...) array: each buffer is its own jit
          argument/result, so the compiled step never threads the pool
          through a ``lax.scan`` carry (which would copy O(pool bytes)
          per call) and buffer donation lets XLA scatter the touched
          rows in place — per-step cache traffic is O(touched bytes);
        * ``block_tables`` (batch, ceil(max_len / page_size)) int32 —
          physical page of each sequence's logical page, 0 = unmapped.
          Owned by the host-side allocator (``repro.serving.kv_pool``),
          overwritten between steps without touching K/V bytes.

        ``kv_dtype="int8"`` (paged only) allocates **quantized pages**:
        the per-layer K/V buffers hold int8 codes and gain
        ``k_scale``/``v_scale`` companions ((n_pages * page_size, Hkv)
        f32, one scale per token row per kv head — see
        ``repro.quant.kv_int8``).  Bytes per page drop from
        ``2·L·ps·Hkv·D·4`` to ``2·L·ps·Hkv·(D + 4)`` — the capacity
        lever ``serving.kv_pool.KVPoolConfig.page_bytes`` accounts for.

        Per-slot lengths are host state (the scheduler's), passed into
        each call as the position vector — the paged cache carries no
        device-side length array.

        Here ``batch`` is the number of *slots* of the running batch —
        which request occupies a slot changes step to step (join/evict)
        with no shape change, hence no recompilation.
        """
        cfg = self.cfg
        if kv_dtype != "fp32" and page_size is None:
            raise ValueError("kv_dtype applies to the paged cache only "
                             "(pass page_size=...)")
        if page_size is not None:
            if not (self.uniform and self.kinds[0] == "attn"
                    and not self.decoder_cross and not cfg.cross_attn_every):
                raise NotImplementedError(
                    "paged KV cache requires a uniform self-attention "
                    f"stack (arch {cfg.name!r} has kinds {self.kinds[:4]})")
            max_pages = -(-max_len // page_size)
            if n_pages is None:
                n_pages = 1 + batch * max_pages   # page 0 is scratch
            hd = cfg.resolved_head_dim
            rows = n_pages * page_size

            def layer():
                if kv_dtype == "int8":
                    return {"self": {
                        "k": jnp.zeros((rows, cfg.n_kv_heads, hd),
                                       jnp.int8),
                        "v": jnp.zeros((rows, cfg.n_kv_heads, hd),
                                       jnp.int8),
                        "k_scale": jnp.zeros((rows, cfg.n_kv_heads),
                                             jnp.float32),
                        "v_scale": jnp.zeros((rows, cfg.n_kv_heads),
                                             jnp.float32)}}
                if kv_dtype != "fp32":
                    raise ValueError(f"kv_dtype={kv_dtype!r}: "
                                     "choose 'fp32' or 'int8'")
                return {"self": {
                    "k": jnp.zeros((rows, cfg.n_kv_heads, hd), cfg.dtype),
                    "v": jnp.zeros((rows, cfg.n_kv_heads, hd), cfg.dtype)}}

            return {
                "block_tables": jnp.zeros((batch, max_pages), jnp.int32),
                "layers": [layer() for _ in range(cfg.n_layers)],
            }
        cl = min(cache_len or max_len, max_len)
        cache: Dict[str, Any] = {"length": jnp.zeros((), jnp.int32)}
        if self.uniform:
            one = self._layer_cache(self.kinds[0], batch, cl, cfg.dtype)
            cache["layers"] = jax.tree.map(
                lambda x: jnp.broadcast_to(
                    x[None], (cfg.n_layers,) + x.shape).copy(), one)
        elif self.block_period:
            p_, nb = self.block_period, self.n_full_blocks
            blocks = []
            for j in range(p_):
                one = self._layer_cache(self.kinds[j], batch, cl, cfg.dtype)
                blocks.append(jax.tree.map(
                    lambda x: jnp.broadcast_to(
                        x[None], (nb,) + x.shape).copy(), one))
            tail = [self._layer_cache(self.kinds[nb * p_ + t], batch, cl,
                                      cfg.dtype)
                    for t in range(self.n_tail)]
            cache["layers"] = {"blocks": blocks, "tail": tail}
        else:
            cache["layers"] = [
                self._layer_cache(kind, batch, cl, cfg.dtype)
                for kind in self.kinds]
        if memory_len:
            cache["memory"] = jnp.zeros((batch, memory_len, cfg.d_model),
                                        cfg.dtype)
        return cache

    # ------------------------------------------------------------------
    # layer stack runners
    # ------------------------------------------------------------------
    def _stack_meta(self):
        cfg = self.cfg
        windows = jnp.asarray(cfg.layer_windows(0), jnp.int32)
        thetas = jnp.asarray(cfg.layer_thetas(), jnp.float32)
        return windows, thetas

    def _run_uniform(self, layers: Params, x: jax.Array,
                     positions: jax.Array, caches: Optional[Params],
                     memory: Optional[jax.Array], *, causal: bool,
                     single_step: bool, window_override: Optional[int],
                     decoder_cross: bool, kind: str,
                     ) -> Tuple[jax.Array, Optional[Params], jax.Array]:
        cfg = self.cfg
        windows, thetas = self._stack_meta()
        if window_override is not None:
            windows = jnp.full_like(windows, window_override)

        fwd = functools.partial(
            _layer_forward, cfg, kind, causal=causal,
            decoder_cross=decoder_cross, single_step=single_step,
            moe_hook=self.moe_hook, decode_hook=self.decode_attn_hook,
            act_constraint=self.attn_act_constraint)
        if cfg.remat and caches is None:   # checkpoint each layer (train)
            fwd = jax.checkpoint(fwd, policy=_remat_policy(cfg))

        if caches is None:
            def body(carry, xs):
                h, aux = carry
                lp, window, theta = xs
                if self.param_constraint is not None:
                    lp = self.param_constraint(lp)
                h, _, a = fwd(lp, h, positions, theta, window, None, memory)
                return (h, aux + a), None

            (x, aux), _ = jax.lax.scan(
                body, (x, jnp.zeros((), jnp.float32)),
                (layers, windows, thetas))
            return x, None, aux

        def body(carry, xs):
            h, aux = carry
            lp, window, theta, cache = xs
            if self.param_constraint is not None:
                lp = self.param_constraint(lp)
            h, new_cache, a = fwd(lp, h, positions, theta, window, cache,
                                  memory)
            return (h, aux + a), new_cache

        (x, aux), new_caches = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)),
            (layers, windows, thetas, caches))
        return x, new_caches, aux

    def _run_blocks(self, layers: Params, x: jax.Array,
                    positions: jax.Array, caches, memory, *, causal: bool,
                    single_step: bool, window_override: Optional[int],
                    ) -> Tuple[jax.Array, Optional[Params], jax.Array]:
        """Scan over super-blocks of a periodic pattern (see __init__)."""
        cfg = self.cfg
        p_, nb = self.block_period, self.n_full_blocks
        windows = list(cfg.layer_windows(0))
        thetas = list(cfg.layer_thetas())
        if window_override is not None:
            windows = [window_override] * cfg.n_layers
        win_rows = jnp.asarray(
            [[windows[b * p_ + j] for j in range(p_)] for b in range(nb)],
            jnp.int32)                                   # (nb, p)
        theta_rows = jnp.asarray(
            [[thetas[b * p_ + j] for j in range(p_)] for b in range(nb)],
            jnp.float32)

        fwd = functools.partial(
            _layer_forward, cfg, causal=causal, single_step=single_step,
            moe_hook=self.moe_hook, decode_hook=self.decode_attn_hook,
            act_constraint=self.attn_act_constraint)

        def block_body(carry, xs):
            h, aux = carry
            lps, wrow, trow, crow = xs
            new_crow = [] if crow is not None else None
            for j in range(p_):
                lp = lps[j]
                if self.param_constraint is not None:
                    lp = self.param_constraint(lp)
                cache_j = None if crow is None else crow[j]
                h, nc, a = fwd(self.kinds[j], lp, h, positions, trow[j],
                               wrow[j], cache_j, memory)
                aux = aux + a
                if new_crow is not None:
                    new_crow.append(nc if nc is not None else {})
            return (h, aux), new_crow

        body = block_body
        if cfg.remat and caches is None:
            body = jax.checkpoint(block_body, policy=_remat_policy(cfg))

        blocks = layers["blocks"]
        cache_blocks = None if caches is None else caches["blocks"]

        def scan_body(carry, xs):
            if caches is None:
                lps, wrow, trow = xs
                return body(carry, (lps, wrow, trow, None))
            lps, wrow, trow, crow = xs
            return body(carry, (lps, wrow, trow, crow))

        xs = ((blocks, win_rows, theta_rows) if caches is None
              else (blocks, win_rows, theta_rows, cache_blocks))
        (x, aux), new_blocks = jax.lax.scan(
            scan_body, (x, jnp.zeros((), jnp.float32)), xs)

        # unrolled remainder layers
        new_tail = None if caches is None else []
        for t in range(self.n_tail):
            i = nb * p_ + t
            lp = layers["tail"][t]
            if self.param_constraint is not None:
                lp = self.param_constraint(lp)
            cache_t = None if caches is None else caches["tail"][t]
            x, nc, a = fwd(self.kinds[i], lp, x, positions,
                           jnp.asarray(thetas[i], jnp.float32),
                           jnp.asarray(windows[i], jnp.int32), cache_t,
                           memory)
            aux = aux + a
            if new_tail is not None:
                new_tail.append(nc if nc is not None else {})
        new_caches = (None if caches is None
                      else {"blocks": new_blocks, "tail": new_tail})
        return x, new_caches, aux

    def _run_pattern(self, layers: List[Params], x: jax.Array,
                     positions: jax.Array, caches: Optional[List],
                     memory: Optional[jax.Array], *, causal: bool,
                     single_step: bool, window_override: Optional[int],
                     ) -> Tuple[jax.Array, Optional[List], jax.Array]:
        cfg = self.cfg
        windows = cfg.layer_windows(0)
        thetas = cfg.layer_thetas()
        aux = jnp.zeros((), jnp.float32)
        new_caches: Optional[List] = None if caches is None else []
        for i, kind in enumerate(self.kinds):
            w = window_override if window_override is not None else windows[i]
            cache_i = None if caches is None else caches[i]
            lp_i = layers[i]
            if self.param_constraint is not None:
                lp_i = self.param_constraint(lp_i)
            fwd = functools.partial(
                _layer_forward, cfg, kind, causal=causal,
                single_step=single_step, moe_hook=self.moe_hook,
                decode_hook=self.decode_attn_hook,
                act_constraint=self.attn_act_constraint)
            if cfg.remat and caches is None:   # per-layer remat (train)
                fwd = jax.checkpoint(fwd)
            x, nc, a = fwd(
                lp_i, x, positions,
                jnp.asarray(thetas[i], jnp.float32),
                jnp.asarray(w, jnp.int32), cache_i, memory)
            aux = aux + a
            if new_caches is not None:
                new_caches.append(nc if nc is not None else {})
        return x, new_caches, aux

    def _run_paged_layers(self, params: Params, x: jax.Array,
                          positions: jax.Array, caches: List, *,
                          single_step: bool,
                          window_override: Optional[int], paged,
                          ) -> Tuple[jax.Array, List, jax.Array]:
        """Unrolled layer loop for the **paged** cache (uniform attn
        stacks only, enforced by ``init_cache``).

        ``caches`` is the per-layer buffer list: every layer's K/V pool
        buffer enters and leaves the jit as its own argument/result
        instead of riding a ``lax.scan`` carry.  The scan variant would
        copy the whole stacked pool once per compiled call (an O(pool
        bytes) floor on every decode step / prefill chunk — ROADMAP:
        measured to dominate chunked prefill at 641 pages); unrolled,
        each buffer's only write is a row scatter, so with the engine's
        buffer donation XLA updates the pool in place and the step costs
        O(touched bytes).  Layer *parameters* stay stacked (L, ...) —
        the per-layer static slice below is the touched-bytes read XLA
        fuses into the layer's matmuls.
        """
        cfg = self.cfg
        windows = list(cfg.layer_windows(0))
        thetas = list(cfg.layer_thetas())
        if window_override is not None:
            windows = [window_override] * cfg.n_layers
        fwd = functools.partial(
            _layer_forward, cfg, self.kinds[0], causal=True,
            single_step=single_step, moe_hook=self.moe_hook,
            decode_hook=self.decode_attn_hook,
            act_constraint=self.attn_act_constraint, paged=paged,
            qmm=self.qmm)
        layers = params["layers"]
        aux = jnp.zeros((), jnp.float32)
        new_caches: List = []
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda a, i=i: a[i], layers)
            if self.param_constraint is not None:
                lp = self.param_constraint(lp)
            x, nc, a = fwd(lp, x, positions,
                           jnp.asarray(thetas[i], jnp.float32),
                           jnp.asarray(windows[i], jnp.int32),
                           caches[i], None)
            aux = aux + a
            new_caches.append(nc if nc is not None else {})
        return x, new_caches, aux

    def _run_layers(self, params: Params, x: jax.Array,
                    positions: jax.Array, caches, memory, *, causal: bool,
                    single_step: bool = False,
                    window_override: Optional[int] = None):
        if self.uniform:
            return self._run_uniform(
                params["layers"], x, positions, caches, memory,
                causal=causal, single_step=single_step,
                window_override=window_override,
                decoder_cross=self.decoder_cross, kind=self.kinds[0])
        if self.block_period:
            return self._run_blocks(
                params["layers"], x, positions, caches, memory,
                causal=causal, single_step=single_step,
                window_override=window_override)
        return self._run_pattern(
            params["layers"], x, positions, caches, memory,
            causal=causal, single_step=single_step,
            window_override=window_override)

    def _encode(self, params: Params, frames: jax.Array) -> jax.Array:
        """Whisper-style encoder over stub frame embeddings (B, F, d)."""
        cfg = self.cfg
        enc = params["encoder"]
        positions = jnp.arange(frames.shape[1])
        windows = jnp.zeros((cfg.n_encoder_layers,), jnp.int32)
        thetas = jnp.full((cfg.n_encoder_layers,), cfg.rope_theta,
                          jnp.float32)
        fwd = functools.partial(_layer_forward, cfg, "attn", causal=False,
                                decoder_cross=False, single_step=False)

        def body(carry, xs):
            lp, window, theta = xs
            h, _, _ = fwd(lp, carry, positions, theta, window, None, None)
            return h, None

        x, _ = jax.lax.scan(body, frames, (enc["layers"], windows, thetas))
        return _apply_norm(cfg, enc["final_norm"], x)

    def _memory_from_batch(self, params: Params, batch: Dict[str, Any],
                           ) -> Optional[jax.Array]:
        if self.cfg.is_encoder_decoder:
            return self._encode(params, batch["frames"])
        if self.cfg.cross_attn_every:
            return batch["image_embeds"]
        return None

    def _embed(self, params: Params, tokens: jax.Array) -> jax.Array:
        """Embedding rows of ``tokens``, times ``embedding_multiplier``."""
        x = jnp.take(params["embed"], tokens, axis=0)
        return _times(x, self.cfg.embedding_multiplier)

    def _unembed(self, params: Params, h: jax.Array) -> jax.Array:
        """Float32 logits of normed hidden states ``h``, divided by
        ``logits_scaling``."""
        cfg = self.cfg
        head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        logits = unembed(head, h, tied=cfg.tie_embeddings)
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        return logits

    def _logits(self, params: Params, x: jax.Array) -> jax.Array:
        return self._unembed(params,
                             _apply_norm(self.cfg, params["final_norm"], x))

    # ------------------------------------------------------------------
    # public entry points
    # ------------------------------------------------------------------
    def _hidden_states(self, params: Params, batch: Dict[str, Any],
                       ) -> Tuple[jax.Array, jax.Array]:
        tokens = batch["tokens"]
        x = self._embed(params, tokens)
        if self.act_constraint is not None:
            x = self.act_constraint(x)
        positions = jnp.arange(tokens.shape[1])
        memory = self._memory_from_batch(params, batch)
        x, _, aux = self._run_layers(params, x, positions, None, memory,
                                     causal=True)
        return _apply_norm(self.cfg, params["final_norm"], x), aux

    def forward(self, params: Params, batch: Dict[str, Any],
                ) -> Tuple[jax.Array, jax.Array]:
        """Teacher-forcing logits over the whole sequence (train)."""
        h, aux = self._hidden_states(params, batch)
        return self._unembed(params, h), aux

    LOSS_CHUNK = 512

    def loss(self, params: Params, batch: Dict[str, Any],
             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Chunked cross entropy: logits are materialised only one
        sequence chunk at a time — full (B, S, V) fp32 logits of a
        256k-vocab model would dwarf every other activation."""
        h, aux = self._hidden_states(params, batch)
        labels = batch["labels"]
        B, S, d = h.shape
        C = min(self.LOSS_CHUNK, S)
        pad = (-S) % C
        if pad:
            h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
            labels = jnp.pad(labels, ((0, 0), (0, pad)),
                             constant_values=-100)
        n_chunks = (S + pad) // C
        hc = jnp.moveaxis(h.reshape(B, n_chunks, C, d), 1, 0)
        yc = jnp.moveaxis(labels.reshape(B, n_chunks, C), 1, 0)

        def body(carry, xs):
            nll_sum, count = carry
            h_i, y_i = xs
            if self.act_constraint is not None:
                h_i = self.act_constraint(h_i)
            lf = self._unembed(params, h_i)
            logz = jax.nn.logsumexp(lf, axis=-1)
            gold = jnp.take_along_axis(
                lf, jnp.clip(y_i, 0)[..., None], axis=-1)[..., 0]
            mask = (y_i != -100).astype(jnp.float32)
            nll_sum = nll_sum + jnp.sum((logz - gold) * mask)
            count = count + jnp.sum(mask)
            return (nll_sum, count), None

        (nll_sum, count), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            (hc, yc))
        ce = nll_sum / jnp.maximum(count, 1.0)
        total = ce + self.cfg.router_aux_coef * aux
        return total, {"ce": ce, "aux": aux}

    def prefill(self, params: Params, batch: Dict[str, Any],
                cache: Dict[str, Any], *,
                window_override: Optional[int] = None,
                ) -> Tuple[jax.Array, Dict[str, Any]]:
        """Process the prompt, fill the cache, return last-token logits."""
        tokens = batch["tokens"]
        x = self._embed(params, tokens)
        if self.act_constraint is not None:
            x = self.act_constraint(x)
        positions = jnp.arange(tokens.shape[1])
        memory = self._memory_from_batch(params, batch)
        x, new_layers, _ = self._run_layers(
            params, x, positions, cache["layers"], memory, causal=True,
            window_override=window_override)
        new_cache = dict(cache)
        new_cache["layers"] = new_layers
        new_cache["length"] = jnp.asarray(tokens.shape[1], jnp.int32)
        if memory is not None:
            new_cache["memory"] = memory
        return self._logits(params, x[:, -1:]), new_cache

    def prefill_paged(self, params: Params, batch: Dict[str, Any],
                      cache: Dict[str, Any], slot: jax.Array,
                      plen: jax.Array, *, page_size: int,
                      start: Optional[jax.Array] = None,
                      ctx_pages: Optional[int] = None,
                      window_override: Optional[int] = None,
                      ) -> Tuple[jax.Array, Dict[str, Any]]:
        """Prefill ONE sequence chunk into batch slot ``slot`` of a
        paged cache.

        ``batch['tokens']`` is (1, Sp) right-padded to any convenient
        bucket length; ``plen`` (traced scalar) is the real chunk
        length, so one compilation per Sp serves every shorter chunk.
        K/V rows land in the physical pages ``cache['block_tables'][slot]``
        maps (padded positions fall through unmapped entries to the
        scratch page).  Returns logits of the *last real* token.

        ``start`` (traced scalar) resumes prefill at an arbitrary
        absolute position offset: the chunk's tokens sit at positions
        ``[start, start + plen)`` and attention runs over the whole
        resident context ``[0, start + plen)``, gathered through the
        block table from ``ctx_pages`` leading pages (static — the
        caller buckets it; pages past the table or past ``kv_len`` are
        masked out).  This is the entry point for **chunked prefill**
        and for resuming after a **prefix-cache** hit, where positions
        ``[0, start)`` were filled by earlier chunks, shared pages, or
        a copy-on-write clone.  ``start=None`` is the one-shot fresh
        path (attends over its own K/V only — identical maths, cheaper).
        """
        tokens = batch["tokens"]
        Sp = tokens.shape[1]
        slot = jnp.asarray(slot, jnp.int32)
        plen = jnp.asarray(plen, jnp.int32)
        x = self._embed(params, tokens)
        offsets = jnp.arange(Sp)
        bt_row = cache["block_tables"][slot]              # (max_pages,)
        if start is None:
            positions = offsets
        else:
            positions = jnp.asarray(start, jnp.int32) + offsets
        phys = bt_row[positions // page_size] * page_size \
            + positions % page_size
        # padding rows go to the scratch page unconditionally: when the
        # padded bucket overruns max_pages * page_size the block-table
        # gather above clamps to the LAST page — a real one — and would
        # clobber cached prompt tokens
        write_slots = jnp.where(offsets < plen, phys,
                                offsets % page_size)
        paged: Dict[str, Any] = {"page_size": page_size,
                                 "write_slots": write_slots}
        if self.paged_head_merge is not None:
            paged["head_merge"] = self.paged_head_merge
        if start is not None:
            if ctx_pages is None:
                raise ValueError("resumed prefill needs static ctx_pages")
            ctx_pos = jnp.arange(ctx_pages * page_size)
            paged["prefill_ctx"] = {
                "phys": bt_row[ctx_pos // page_size] * page_size
                        + ctx_pos % page_size,
                "kv_len": jnp.asarray(start, jnp.int32) + plen,
                "q_offset": jnp.asarray(start, jnp.int32),
            }
        x, new_layers, _ = self._run_paged_layers(
            params, x, positions, cache["layers"], single_step=False,
            window_override=window_override, paged=paged)
        new_cache = dict(cache)
        new_cache["layers"] = new_layers
        last = jax.lax.dynamic_slice_in_dim(x, plen - 1, 1, axis=1)
        return self._logits(params, last), new_cache

    def decode_step(self, params: Params, cache: Dict[str, Any],
                    tokens: jax.Array, pos: jax.Array, *,
                    window_override: Optional[int] = None,
                    page_size: Optional[int] = None,
                    ) -> Tuple[jax.Array, Dict[str, Any]]:
        """One decode step.  tokens (B, 1).

        Ring cache: ``pos`` is a *scalar* absolute position shared by
        the whole (lockstep) batch.  Paged cache (``page_size`` given):
        ``pos`` is a **vector** (B,) of per-request absolute positions —
        requests in different decode phases share one step; ``pos[b] < 0``
        marks an idle slot (its write goes to the scratch page and its
        attention is fully masked).
        """
        if page_size is not None:
            return self._decode_step_paged(
                params, cache, tokens, pos, page_size=page_size,
                window_override=window_override)
        x = self._embed(params, tokens)
        if self.cache_constraint is not None:
            cache = self.cache_constraint(cache)
        positions = pos + jnp.arange(1)
        memory = cache.get("memory")
        x, new_layers, _ = self._run_layers(
            params, x, positions, cache["layers"], memory, causal=True,
            single_step=True, window_override=window_override)
        new_cache = dict(cache)
        new_cache["layers"] = new_layers
        new_cache["length"] = (pos + 1).astype(jnp.int32)
        if self.cache_constraint is not None:
            new_cache = self.cache_constraint(new_cache)
        return self._logits(params, x), new_cache

    def _decode_step_paged(self, params: Params, cache: Dict[str, Any],
                           tokens: jax.Array, pos: jax.Array, *,
                           page_size: int,
                           window_override: Optional[int] = None,
                           ) -> Tuple[jax.Array, Dict[str, Any]]:
        pos = jnp.asarray(pos, jnp.int32)                 # (B,)
        safe_pos = jnp.maximum(pos, 0)
        x = self._embed(params, tokens)                   # (B, 1, d)
        bt = cache["block_tables"]
        B = bt.shape[0]
        phys = bt[jnp.arange(B), safe_pos // page_size] * page_size \
            + safe_pos % page_size                        # (B,)
        # idle lanes (pos < 0) MUST land on the scratch page even when
        # their slot's block table is populated (a sequence that was
        # prefilled this step but isn't decoding yet would otherwise get
        # its first page clobbered by the lane's garbage write)
        write_slots = jnp.where(pos >= 0, phys, safe_pos % page_size)
        kv_len = jnp.maximum(pos + 1, 0)
        paged = {"page_size": page_size, "write_slots": write_slots,
                 "block_tables": bt, "kv_len": kv_len}
        if self.paged_head_merge is not None:
            paged["head_merge"] = self.paged_head_merge
        positions = safe_pos[:, None]                     # (B, 1) for RoPE
        x, new_layers, _ = self._run_paged_layers(
            params, x, positions, cache["layers"], single_step=True,
            window_override=window_override, paged=paged)
        new_cache = dict(cache)
        new_cache["layers"] = new_layers
        return self._logits(params, x), new_cache

    def verify_step(self, params: Params, cache: Dict[str, Any],
                    tokens: jax.Array, pos: jax.Array, n_fed: jax.Array, *,
                    page_size: int,
                    window_override: Optional[int] = None,
                    ) -> Tuple[jax.Array, Dict[str, Any]]:
        """Speculative multi-token verify against the paged cache.

        ``tokens`` (B, S) feeds each lane its last sampled token plus up
        to S - 1 draft tokens; ``pos`` (B,) is the absolute position of
        column 0 (the last token's write position, as in decode;
        ``pos[b] < 0`` marks an idle lane); ``n_fed`` (B,) is how many
        leading columns of the lane are real (1 = plain decode riding
        along, 1 + m = m draft tokens).  Columns past ``n_fed`` and idle
        lanes write to the scratch page and their logits are garbage the
        caller must ignore.

        Returns logits (B, S, vocab): column j scores position
        ``pos + j`` having seen exactly the context a sequential decode
        would have — the attention read at offset j uses
        ``kv_len = pos + 1 + j`` over rows this same call scattered —
        so ``argmax(logits[b, j])`` equals the token a non-speculative
        engine would emit after accepting the first j draft tokens.
        That identity is the byte-parity guarantee of ``--spec-decode``.
        """
        pos = jnp.asarray(pos, jnp.int32)                 # (B,)
        n_fed = jnp.asarray(n_fed, jnp.int32)             # (B,)
        safe_pos = jnp.maximum(pos, 0)
        x = self._embed(params, tokens)                   # (B, S, d)
        bt = cache["block_tables"]
        B, S = tokens.shape
        offs = jnp.arange(S, dtype=jnp.int32)[None, :]    # (1, S)
        positions = safe_pos[:, None] + offs              # (B, S)
        phys = bt[jnp.arange(B)[:, None], positions // page_size] \
            * page_size + positions % page_size           # (B, S)
        # scratch-route the same lanes decode does (idle slots) PLUS the
        # columns past each lane's real feed — a lane drafting m < S - 1
        # tokens has no page grant (and no token) for the tail columns
        valid = (pos[:, None] >= 0) & (offs < n_fed[:, None])
        write_slots = jnp.where(valid, phys,
                                positions % page_size).reshape(B * S)
        kv_len = jnp.maximum(pos + 1, 0)
        paged = {"page_size": page_size, "write_slots": write_slots,
                 "block_tables": bt, "kv_len": kv_len, "verify": True}
        if self.paged_head_merge is not None:
            paged["head_merge"] = self.paged_head_merge
        x, new_layers, _ = self._run_paged_layers(
            params, x, positions, cache["layers"], single_step=False,
            window_override=window_override, paged=paged)
        new_cache = dict(cache)
        new_cache["layers"] = new_layers
        return self._logits(params, x), new_cache


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
