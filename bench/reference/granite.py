"""Plain reference of a Granite decoder (``GraniteForCausalLM``).

Straight ``jax.numpy`` with no kernel, cache, paging or batching, and
nothing imported from the program.  It is the dense block of
``dense.py`` (whose matmul, RMSNorm and RoPE it reuses) with Granite's
four scalars, read from the configuration's keys::

    x0 = e · E[tokens]                                 embedding_multiplier e
    h  = x + r · Wo · attn(rope(Wq · n1(x)), rope(Wk · n1(x)), Wv · n1(x))
    x' = h + r · Wd · (silu(Wg · n2(h)) * (Wu · n2(h)))   residual_multiplier r
    attn scores = (q · k) · a                          attention_multiplier a
    logits = n_f(x) · E^T / s   (tied; W_head untied)  logits_scaling s

causal, GQA, rotate-half RoPE, ``n(x) = x / rms(x) * (1 + g)``.  A key
that is absent takes a plain block's value: ``a = 1/sqrt(head_dim)``,
``e = r = s = 1``, and the logits are then those of ``dense.py``.

Departures from Hugging Face ``GraniteForCausalLM``:

* precision: every step in float32 and every matmul at
  ``Precision.HIGHEST`` (HF runs the checkpoint's bf16 with its own
  upcasts), or the int8 control below;
* RMSNorm gains are stored as an offset ``g`` and applied as ``1 + g``
  (HF stores the gain itself): the same function of the stored tree;
* no attention or residual dropout, no projection biases
  (``attention_bias`` and ``mlp_bias`` false), no padding mask: one
  unpadded sequence at positions ``0..S-1``;
* the tied head reads the unscaled embedding matrix, as HF's does.

Two precisions, as in ``dense.py``:

* ``"f32"``: the reference;
* ``"int8"``: every weight matmul quantized to int8 on both sides
  (weights per output column, activations per row) with int32
  accumulation, the scalars still applied in float32 — the control,
  one precision step below the bf16 the configuration states.

The stack runs one jitted layer at a time over the stacked weights, so
no float32 copy of the whole model exists.
"""

from __future__ import annotations

import functools
from typing import Mapping

import jax
import jax.numpy as jnp

from bench.reference.dense import HIGHEST, _mm, _norm, _rope


@functools.partial(jax.jit, static_argnames=("dims", "prec"))
def _layer(x, layers, i, *, dims, prec):
    (hq, hkv, D, eps, theta, qk_norm, attn_mult, resid_mult) = dims
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
    s = jnp.einsum("qhgd,khd->hgqk", qg, k, precision=HIGHEST) * attn_mult
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hgqk,khd->qhgd", p, v, precision=HIGHEST).reshape(S, -1)
    x = x + resid_mult * _mm(o, a["w_o"], prec)
    m = lp["mlp"]
    h2 = _norm(x, lp["ln2"]["g"], eps)
    f = jax.nn.silu(_mm(h2, m["w_gate"], prec)) * _mm(h2, m["w_up"], prec)
    return x + resid_mult * _mm(f, m["w_down"], prec)


@functools.partial(jax.jit, static_argnames=("eps", "tied", "prec",
                                             "scaling"))
def _head(x, rows, final_g, head, *, eps, tied, prec, scaling):
    h = _norm(x[rows], final_g, eps)
    w = head.T if tied else head
    return _mm(h, w, prec) / scaling


def logits_at(params, conf: Mapping, tokens, rows, *, prec: str = "f32"):
    """Logits (len(rows), vocab) in float32 of the sequence ``tokens``
    (S,) at the positions ``rows``: row ``r`` predicts token ``r + 1``.
    Padding after the real tokens changes nothing before it (causal)."""
    d = int(conf["hidden_size"])
    hq = int(conf["num_attention_heads"])
    D = int(conf.get("head_dim") or d // hq)
    dims = (hq, int(conf["num_key_value_heads"]), D,
            float(conf.get("rms_norm_eps", 1e-6)),
            float(conf.get("rope_theta", 10_000.0)),
            bool(conf.get("qk_norm", False)),
            float(conf.get("attention_multiplier", D ** -0.5)),
            float(conf.get("residual_multiplier", 1.0)))
    x = jnp.take(params["embed"], jnp.asarray(tokens), axis=0)
    x = x.astype(jnp.float32) * float(conf.get("embedding_multiplier", 1.0))
    for i in range(int(conf["num_hidden_layers"])):
        x = _layer(x, params["layers"], jnp.int32(i), dims=dims, prec=prec)
    tied = bool(conf.get("tie_word_embeddings", False))
    head = params["embed"] if tied else params["lm_head"]
    return _head(x, jnp.asarray(rows), params["final_norm"]["g"], head,
                 eps=dims[3], tied=tied, prec=prec,
                 scaling=float(conf.get("logits_scaling", 1.0)))
