"""Plain reference of a dense decoder (Llama / Qwen3 / Granite block).

Straight ``jax.numpy`` with no kernel, cache, paging or batching, and
nothing imported from the program.  Per layer::

    h  = x + Wo · attn(rope(norm_qk(Wq · n1(x))), rope(norm_qk(Wk · n1(x))),
                       Wv · n1(x))                    causal, GQA, 1/sqrt(D)
    x' = h + Wd · (silu(Wg · n2(h)) * (Wu · n2(h)))

with ``n(x) = x / rms(x) * (1 + g)`` and rotate-half RoPE.  Logits are
``n_f(x) · E^T`` (tied) or ``n_f(x) · W_head``.

Two precisions:

* ``"f32"``: weights cast to float32, every matmul at
  ``Precision.HIGHEST`` — the reference;
* ``"int8"``: every weight matmul quantized to int8 on both sides
  (weights per output column, activations per row, absmax scaling) with
  int32 accumulation — the control, one precision step below the bf16
  the configuration states.

The stack runs one jitted layer at a time over the stacked weights, so
no float32 copy of the whole model exists.
"""

from __future__ import annotations

import functools
from typing import Mapping

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(x, w, prec):
    """``x @ w`` with ``w`` stored (d_in, d_out) in the served dtype."""
    if prec == "int8":
        xf = x.astype(jnp.float32)
        wf = w.astype(jnp.float32)
        xs = jnp.maximum(jnp.max(jnp.abs(xf), -1, keepdims=True), 1e-30) / 127
        ws = jnp.maximum(jnp.max(jnp.abs(wf), 0, keepdims=True), 1e-30) / 127
        xq = jnp.round(xf / xs).astype(jnp.int8)
        wq = jnp.round(wf / ws).astype(jnp.int8)
        acc = jax.lax.dot_general(
            xq, wq, (((xq.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32) * xs * ws
    return jnp.matmul(x, w.astype(jnp.float32), precision=HIGHEST)


def _norm(x, g, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g.astype(jnp.float32))


def _rope(x, theta):
    """x (S, H, D), positions 0..S-1, rotate-half convention."""
    S, _, D = x.shape
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("dims", "prec"))
def _layer(x, layers, i, *, dims, prec):
    (hq, hkv, D, eps, theta, qk_norm) = dims
    lp = jax.tree.map(lambda a: a[i], layers)
    S = x.shape[0]
    a = lp["attn"]
    h = _norm(x, lp["ln1"]["g"], eps)
    q = _mm(h, a["w_q"], prec).reshape(S, hq, D)
    k = _mm(h, a["w_k"], prec).reshape(S, hkv, D)
    v = _mm(h, a["w_v"], prec).reshape(S, hkv, D)
    if qk_norm:
        q = _norm(q, a["q_norm"], eps)
        k = _norm(k, a["k_norm"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    G = hq // hkv
    qg = q.reshape(S, hkv, G, D)
    s = jnp.einsum("qhgd,khd->hgqk", qg, k, precision=HIGHEST) * D ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hgqk,khd->qhgd", p, v, precision=HIGHEST).reshape(S, -1)
    x = x + _mm(o, a["w_o"], prec)
    m = lp["mlp"]
    h2 = _norm(x, lp["ln2"]["g"], eps)
    f = jax.nn.silu(_mm(h2, m["w_gate"], prec)) * _mm(h2, m["w_up"], prec)
    return x + _mm(f, m["w_down"], prec)


@functools.partial(jax.jit, static_argnames=("eps", "tied", "prec"))
def _head(x, rows, final_g, head, *, eps, tied, prec):
    h = _norm(x[rows], final_g, eps)
    w = head.T if tied else head
    return _mm(h, w, prec)


def logits_at(params, conf: Mapping, tokens, rows, *, prec: str = "f32"):
    """Logits (len(rows), vocab) in float32 of the sequence ``tokens``
    (S,) at the positions ``rows``: row ``r`` predicts token ``r + 1``.
    Padding after the real tokens changes nothing before it (causal)."""
    d = int(conf["hidden_size"])
    hq = int(conf["num_attention_heads"])
    dims = (hq, int(conf["num_key_value_heads"]),
            int(conf.get("head_dim") or d // hq),
            float(conf.get("rms_norm_eps", 1e-6)),
            float(conf.get("rope_theta", 10_000.0)),
            bool(conf.get("qk_norm", False)))
    x = jnp.take(params["embed"], jnp.asarray(tokens), axis=0)
    x = x.astype(jnp.float32)
    for i in range(int(conf["num_hidden_layers"])):
        x = _layer(x, params["layers"], jnp.int32(i), dims=dims, prec=prec)
    tied = bool(conf.get("tie_word_embeddings", False))
    head = params["embed"] if tied else params["lm_head"]
    return _head(x, jnp.asarray(rows), params["final_norm"]["g"], head,
                 eps=dims[3], tied=tied, prec=prec)
