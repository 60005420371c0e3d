"""A configuration file to the program's model config, and the base weights
and LoRA factors made by the benchmark from the seed.

Weights are made on the device in one jitted call, in the type they are
served in (bf16), in the program's parameter layout: ``embed``,
``final_norm``, ``lm_head`` (untied only) and one stacked segment of blocks
``{ln1, attn: {wq, wk, wv, wo, bq, bk, bv}, ln2, mlp: {w_gate, w_up,
w_down}}``.  The plain reference reads the same arrays, so the reference
never takes weights the program made.
"""
from __future__ import annotations

import functools
import json
import os
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def program_config(c: dict):
    """The program's ``ModelConfig`` for a Qwen2-family configuration file
    (published ``config.json`` keys)."""
    from repro.common.config import ModelConfig

    return ModelConfig(
        name=c["name"], family="dense",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        qkv_bias=bool(c.get("qkv_bias", True)),
        tie_embeddings=bool(c.get("tie_word_embeddings", False)),
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[c["torch_dtype"]],
        source=c["source"])


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def _weights(c_items, key):
    c = dict(c_items)
    d, V, L = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"]
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    hd, ff = d // H, c["intermediate_size"]
    dt = jnp.dtype(c["torch_dtype"])
    ks = iter(jax.random.split(key, 16))

    def mat(shape, fan_in):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dt)

    def vec(shape, mean, std):
        return (mean + std * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(dt)

    attn = {"wq": mat((L, d, H * hd), d), "wk": mat((L, d, K * hd), d),
            "wv": mat((L, d, K * hd), d), "wo": mat((L, H * hd, d), H * hd),
            "bq": vec((L, H * hd), 0.0, 0.1), "bk": vec((L, K * hd), 0.0, 0.1),
            "bv": vec((L, K * hd), 0.0, 0.1)}
    blocks = {"ln1": vec((L, d), 1.0, 0.1), "attn": attn,
              "ln2": vec((L, d), 1.0, 0.1),
              "mlp": {"w_gate": mat((L, d, ff), d), "w_up": mat((L, d, ff), d),
                      "w_down": mat((L, ff, d), ff)}}
    w = {"embed": vec((V, d), 0.0, 0.02), "final_norm": vec((d,), 1.0, 0.1),
         "blocks": (blocks,)}
    if not c.get("tie_word_embeddings"):
        w["lm_head"] = mat((d, V), d)
    return w


@functools.lru_cache(maxsize=None)
def _weights_fn(c_items):
    return jax.jit(functools.partial(_weights, c_items))


def config_items(c: dict):
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str, bool))))


def make_weights(c: dict, seed: int) -> Dict:
    """Base weights from the seed, on the device, in one jitted call."""
    return _weights_fn(config_items(c))(jax.random.fold_in(seed_key(seed), 1))


def target_dims(c: dict, target: str):
    d = c["hidden_size"]
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // H
    return {"wq": (d, H * hd), "wk": (d, K * hd), "wv": (d, K * hd),
            "wo": (H * hd, d)}[target]


def lora_tree(c: dict, factors: Dict) -> Dict:
    """The program's adapter tree from {target: (A, B, scale)}: the leaves
    mirror the base weights' path, with the block segment keyed 0."""
    return {"blocks": {0: {"attn": {t: {"A": A, "B": B, "scale": s}
                                    for t, (A, B, s) in factors.items()}}}}


def lora_factors(tree: Dict) -> Dict:
    """Inverse of :func:`lora_tree`."""
    return {t: (v["A"], v["B"], v["scale"])
            for t, v in tree["blocks"][0]["attn"].items()}
