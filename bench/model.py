"""The system under test, built from a configuration file.

Maps Hugging Face ``config.json`` keys onto the program's
``ModelConfig``.  A key the program cannot honour is refused here, so
a configuration never runs as something other than what its file
states.
"""

from __future__ import annotations

import math
from typing import Mapping

#: scalars a plain Llama-style block implies; a Granite file must state
#: these values to run on the program's dense block
_NEUTRAL = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
            "logits_scaling": 1.0}


def model_config(name: str, conf: Mapping):
    import jax.numpy as jnp

    from repro.models.config import ModelConfig
    if conf.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{name}: hidden_act {conf['hidden_act']!r}")
    if conf.get("attention_bias") or conf.get("mlp_bias"):
        raise ValueError(f"{name}: projection biases are not run")
    for key, want in _NEUTRAL.items():
        if key in conf and float(conf[key]) != want:
            raise ValueError(f"{name}: {key}={conf[key]} is not applied "
                             "by the program's dense block")
    hd = int(conf.get("head_dim")
             or conf["hidden_size"] // conf["num_attention_heads"])
    if "attention_multiplier" in conf and not math.isclose(
            float(conf["attention_multiplier"]), hd ** -0.5):
        raise ValueError(f"{name}: attention_multiplier must be "
                         f"1/sqrt(head_dim) = {hd ** -0.5}")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        conf.get("torch_dtype", "bfloat16")]
    return ModelConfig(
        name=name, arch_type="dense",
        n_layers=int(conf["num_hidden_layers"]),
        d_model=int(conf["hidden_size"]),
        n_heads=int(conf["num_attention_heads"]),
        n_kv_heads=int(conf["num_key_value_heads"]),
        head_dim=hd,
        d_ff=int(conf["intermediate_size"]),
        vocab_size=int(conf["vocab_size"]),
        qk_norm=bool(conf.get("qk_norm", False)),
        rope_theta=float(conf.get("rope_theta", 10_000.0)),
        norm_eps=float(conf.get("rms_norm_eps", 1e-6)),
        tie_embeddings=bool(conf.get("tie_word_embeddings", False)),
        dtype=dtype)


def build(name: str, conf: Mapping):
    from repro.models import build_model
    return build_model(model_config(name, conf))
