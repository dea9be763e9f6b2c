"""Random weights from the seed, made on the device in one jitted call.

The tree is laid out from the configuration file alone (no program
code), in the layout the dense decoder takes: projections stored
``(d_in, d_out)`` and stacked over layers, RMSNorm gains stored as an
offset ``g`` applied as ``1 + g``.  The plain reference reads the same
tree, so both sides get identical weights from one seed without either
taking anything the other made.

Every leaf is drawn in the served dtype directly (bf16), leaf by leaf
from ``fold_in(key, i)``: no float32 copy of the model ever exists.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Mapping, Tuple

import numpy as np

from bench import flops

#: spread of the RMSNorm gains around 1
NORM_STD = 0.1
EMBED_STD = 0.02


def seed32(seed: int, purpose: str) -> int:
    """A 32-bit key for ``purpose`` derived from any whole ``seed``."""
    tag = int.from_bytes(purpose.encode(), "little") % (2 ** 63)
    return int(np.random.SeedSequence([int(seed) % (2 ** 64), tag])
               .generate_state(1)[0])


def layout(conf: Mapping) -> Dict[str, Any]:
    """``{path: (shape, init std or None for 1/sqrt(fan_in))}``."""
    s = flops.shapes(conf)
    L, d, hd, f, V = s["layers"], s["d"], s["head_dim"], s["d_ff"], s["vocab"]
    q, kv = s["q_heads"] * hd, s["kv_heads"] * hd
    out: Dict[Tuple[str, ...], Tuple[Tuple[int, ...], Any]] = {
        ("embed",): ((V, d), EMBED_STD),
        ("final_norm", "g"): ((d,), NORM_STD),
        ("layers", "ln1", "g"): ((L, d), NORM_STD),
        ("layers", "attn", "w_q"): ((L, d, q), None),
        ("layers", "attn", "w_k"): ((L, d, kv), None),
        ("layers", "attn", "w_v"): ((L, d, kv), None),
        ("layers", "attn", "w_o"): ((L, q, d), None),
        ("layers", "ln2", "g"): ((L, d), NORM_STD),
        ("layers", "mlp", "w_gate"): ((L, d, f), None),
        ("layers", "mlp", "w_up"): ((L, d, f), None),
        ("layers", "mlp", "w_down"): ((L, f, d), None),
    }
    if s["qk_norm"]:
        out[("layers", "attn", "q_norm")] = ((L, hd), NORM_STD)
        out[("layers", "attn", "k_norm")] = ((L, hd), NORM_STD)
    if not s["tied"]:
        out[("lm_head",)] = ((d, V), None)
    return out


def _nest(flat: Dict[Tuple[str, ...], Any]) -> Dict[str, Any]:
    tree: Dict[str, Any] = {}
    for path, v in flat.items():
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = v
    return tree


def make(conf: Mapping, seed: int):
    """The whole weight tree for ``seed``, on the default device, in the
    dtype the configuration serves (``torch_dtype``)."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(conf.get("torch_dtype", "bfloat16"))
    spec = layout(conf)

    def draw(key):
        flat = {}
        for i, (path, (shape, std)) in enumerate(sorted(spec.items())):
            k = jax.random.fold_in(key, i)
            if std is None:
                std = 1.0 / math.sqrt(shape[-2])
            flat[path] = (jax.random.normal(k, shape, dtype)
                          * jnp.asarray(std, dtype))
        return _nest(flat)

    key = jax.random.PRNGKey(seed32(seed, "weights"))
    return jax.jit(draw)(key)
