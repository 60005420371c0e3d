"""Operations and bytes of the algorithm, computed from shapes.

These never come from ``cost_analysis`` of a compiled program: a roofline
share or an MFU must read the same work whatever implements it.  All counts
are for the dense grouped-query block (Qwen2 family) that the benchmark's
configurations use: ``q/k/v/o`` projections with biases, a gated MLP, and an
output head over the vocabulary (tied or untied).

Conventions: a multiply-add is 2 operations; attention is causal, so a
sequence of ``S`` tokens scores ``S (S + 1) / 2`` query-key pairs; weights
are bf16 (2 bytes) and LoRA optimizer state is fp32.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence, Tuple

BF16 = 2
FP32 = 4


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


def least_time(ops: float, nbytes: float, peaks: dict) -> Tuple[float, str]:
    """The roofline's least time for the work and which bound sets it."""
    t_c = ops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
