"""Operations and bytes that a piece of serving work needs, from the
shapes of a configuration file alone (Hugging Face ``config.json``
keys).  Nothing here reads the program: these counts are the
yardstick the per-layer metrics divide by.

A dense decoder layer holds q/k/v/o projections, a SwiGLU MLP and two
RMSNorm gains (plus per-head q/k norm gains where the family has
them).  Counts are per token; a matmul of an ``m``-vector by an
``m x n`` matrix is ``2 m n`` operations.
"""

from __future__ import annotations

from typing import Dict, Iterable, Mapping

BF16_BYTES = 2
F32_BYTES = 4


def shapes(conf: Mapping) -> Dict[str, int]:
    """The widths every count below is built from."""
    d = int(conf["hidden_size"])
    hd = int(conf.get("head_dim") or d // int(conf["num_attention_heads"]))
    return {
        "layers": int(conf["num_hidden_layers"]),
        "d": d,
        "head_dim": hd,
        "q_heads": int(conf["num_attention_heads"]),
        "kv_heads": int(conf["num_key_value_heads"]),
        "d_ff": int(conf["intermediate_size"]),
        "vocab": int(conf["vocab_size"]),
        "tied": bool(conf.get("tie_word_embeddings", False)),
        "qk_norm": bool(conf.get("qk_norm", False)),
    }


def param_counts(conf: Mapping) -> Dict[str, int]:
    """Parameters by part: one layer's matmul weights, one layer's norm
    gains, the embedding, the output head (0 when tied) and the total."""
    s = shapes(conf)
    d, hd = s["d"], s["head_dim"]
    q, kv = s["q_heads"] * hd, s["kv_heads"] * hd
    layer_mm = d * q + 2 * d * kv + q * d + 3 * d * s["d_ff"]
    layer_norm = 2 * d + (2 * hd if s["qk_norm"] else 0)
    embed = s["vocab"] * d
    head = 0 if s["tied"] else s["vocab"] * d
    total = s["layers"] * (layer_mm + layer_norm) + embed + head + d
    return {"layer_matmul": layer_mm, "layer_norm": layer_norm,
            "embed": embed, "head": head, "total": total}


def kv_bytes_per_token(conf: Mapping, dtype_bytes: int = BF16_BYTES) -> int:
    """K and V of one token over every layer."""
    s = shapes(conf)
    return s["layers"] * 2 * s["kv_heads"] * s["head_dim"] * dtype_bytes


def attn_flops(conf: Mapping, context: int) -> int:
    """Score and value products of ONE query token over ``context``
    keys, over every layer: ``QK^T`` and ``PV`` are ``2 c D`` each per
    query head."""
    s = shapes(conf)
    return s["layers"] * 4 * context * s["q_heads"] * s["head_dim"]


def token_flops(conf: Mapping, context: int, *, logits: bool) -> int:
    """Model operations of one token that attends over ``context``
    positions (itself included); ``logits`` adds the output head, which
    the program runs only for tokens it samples from."""
    s = shapes(conf)
    p = param_counts(conf)
    out = 2 * s["layers"] * p["layer_matmul"] + attn_flops(conf, context)
    if logits:
        out += 2 * s["vocab"] * s["d"]
    return out


def prefill_chunk_flops(conf: Mapping, start: int, n: int, *,
                        last: bool) -> int:
    """A prefill chunk of ``n`` real tokens at positions
    ``[start, start + n)``: token ``i`` attends over ``start + i + 1``
    positions; only the last chunk's last token gets logits."""
    s = shapes(conf)
    p = param_counts(conf)
    ctx_sum = n * start + n * (n + 1) // 2
    out = (2 * s["layers"] * p["layer_matmul"] * n
           + s["layers"] * 4 * ctx_sum * s["q_heads"] * s["head_dim"])
    if last:
        out += 2 * s["vocab"] * s["d"]
    return out


def decode_step_flops(conf: Mapping, kv_lens: Iterable[int]) -> int:
    """One batched decode step: one token per live lane, lane ``b``
    attending over ``kv_lens[b]`` positions, logits for every lane."""
    return sum(token_flops(conf, int(c), logits=True) for c in kv_lens)


def paged_attn_work(conf: Mapping, kv_lens: Iterable[int]) -> Dict[str, int]:
    """What the paged decode attention of one step needs at the least,
    over every layer: the live context of each lane (``kv_len`` rows of
    K and V in bf16) plus its query (bf16) and output (f32).  Pages the
    kernel reads past ``kv_len`` are not work, so a kernel that stops
    reading them is scored on the same count."""
    s = shapes(conf)
    lens = [int(c) for c in kv_lens]
    per_q = s["q_heads"] * s["head_dim"]
    kv = sum(lens) * kv_bytes_per_token(conf)
    qo = s["layers"] * len(lens) * per_q * (BF16_BYTES + F32_BYTES)
    return {"bytes": kv + qo,
            "flops": sum(attn_flops(conf, c) for c in lens)}


def roofline_seconds(flops: float, nbytes: float, peaks: Mapping) -> Dict:
    """Least time of work at the chip's peaks, and which bound sets it."""
    t_c = flops / float(peaks["bf16_flops_per_s"])
    t_m = nbytes / float(peaks["hbm_bytes_per_s"])
    return {"seconds": max(t_c, t_m),
            "bound": "compute" if t_c >= t_m else "memory"}
