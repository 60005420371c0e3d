"""The Qwen2 block (arXiv:2407.10671; also Qwen2.5): grouped-query attention
with q/k/v biases, a SiLU-gated MLP, RMSNorm, an output head over the
vocabulary (tied or untied).

Weights are laid out as the program's dense family holds them: ``embed``,
``final_norm``, ``lm_head`` (untied only) and one stacked segment of blocks
``{ln1, attn: {wq, wk, wv, wo, bq, bk, bv}, ln2, mlp: {w_gate, w_up,
w_down}}``.  The reference's LoRA keys are the bare target names.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import BF16, FP32


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


def weights(c: dict, key) -> Dict:
    """Matrices N(0, 1/fan_in), embedding N(0, 0.02^2), norms 1 + N(0, 0.1^2),
    biases N(0, 0.1^2), in ``torch_dtype``."""
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


# -- the adapter tree ----------------------------------------------------------

_SEGMENT = ("blocks", 0, "attn")


def lora_key(path: Tuple) -> str:
    """The reference's key of the program's adapter at ``path``: the bare
    target name, in the one stacked segment."""
    if tuple(path[:-1]) != _SEGMENT:
        raise ValueError(f"adapter leaf {path} is not in the one attention "
                         f"segment {_SEGMENT} of the Qwen2 layout")
    return path[-1]


def lora_path(key: str) -> Tuple:
    """Inverse of :func:`lora_key`."""
    return _SEGMENT + (key,)


# -- operations and bytes ------------------------------------------------------


@dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        return cls(cfg["num_hidden_layers"], cfg["hidden_size"],
                   cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["hidden_size"] // cfg["num_attention_heads"],
                   cfg["intermediate_size"], cfg["vocab_size"],
                   bool(cfg.get("tie_word_embeddings", False)))

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim

    def target_shape(self, target: str) -> Tuple[int, int]:
        """(d_in, d_out) of one LoRA target projection."""
        d = self.d_model
        return {"wq": (d, self.q_dim), "wk": (d, self.kv_dim),
                "wv": (d, self.kv_dim), "wo": (self.q_dim, d)}[target]

    @property
    def block_matmul_params(self) -> int:
        """Matmul weights of one layer (projections and MLP)."""
        d = self.d_model
        return (2 * d * self.q_dim + 2 * d * self.kv_dim
                + 3 * d * self.d_ff)

    @property
    def weight_bytes(self) -> int:
        """Every weight of the model in bf16: blocks, norms, biases,
        embedding and (if untied) the head."""
        d = self.d_model
        per_layer = (self.block_matmul_params + 2 * d
                     + self.q_dim + 2 * self.kv_dim)
        emb = self.vocab * d * (1 if self.tied else 2)
        return BF16 * (self.layers * per_layer + emb + d)


def lora_rank_width(dims: Dims, targets: Sequence[str]) -> int:
    """sum over targets of (d_in + d_out): a rank-1 adapter's parameters per
    layer."""
    return sum(sum(dims.target_shape(t)) for t in targets)


def train_step_work(dims: Dims, rows: int, seq: int, rank: int,
                    targets: Sequence[str]) -> Tuple[float, float]:
    """(operations, bytes) of one LoRA train step on ``rows`` sequences of
    ``seq`` tokens.

    Counted: the forward pass, the backward pass's activation gradients
    through the frozen weights (so 4 N per token, not 6 N: frozen weights
    get no gradient), the adapters' forward (2 r (d_in + d_out) per token)
    and backward (weight and input gradients, twice that), causal attention
    forward (scores and values) and backward (twice the forward), and the
    head over every predicted position.  Recomputed operations do not count.

    Bytes: the weights read once forward and once backward, and the
    adapters with their AdamW state (parameters, moments, gradient: fp32)
    read and written once; activations are taken to stay on chip, so the
    byte count is the least any implementation moves.
    """
    L, T = dims.layers, rows * seq
    pred = rows * (seq - 1)
    pairs = rows * seq * (seq + 1) // 2
    attn_fwd = L * 2 * 2 * dims.q_dim * pairs
    head = 2 * dims.d_model * dims.vocab * pred
    blocks = 2 * L * dims.block_matmul_params * T
    lora = 2 * L * rank * lora_rank_width(dims, targets) * T
    forward = blocks + head + attn_fwd + lora
    backward = blocks + head + 2 * attn_fwd + 2 * lora
    ops = forward + backward
    lora_params = L * rank * lora_rank_width(dims, targets)
    nbytes = 2 * dims.weight_bytes + 7 * FP32 * lora_params
    return float(ops), float(nbytes)
