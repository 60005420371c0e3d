"""The operation and byte counts against hand counts, for one shape of each
configuration."""
import json
import os

import pytest

from bench import flops
from bench.families import qwen2

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def dims(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return qwen2.Dims.from_config(json.load(f))


def test_qwen2_0p5b_block_and_weights():
    d = dims("qwen2-0.5b")
    # q and o: 896 x 896 each; k and v: 896 x 128 each; MLP: 3 x 896 x 4864
    assert d.block_matmul_params == 2 * 802_816 + 2 * 114_688 + 13_074_432
    assert d.block_matmul_params == 14_909_440
    # per layer, plus norms (2 x 896) and biases (896 + 2 x 128); one tied
    # embedding of 151,936 x 896 and the final norm, two bytes each
    per_layer = 14_909_440 + 1_792 + 1_152
    assert d.weight_bytes == 2 * (24 * per_layer + 136_134_656 + 896)


def test_qwen2_0p5b_train_step_by_hand():
    d = dims("qwen2-0.5b")
    ops, nbytes = qwen2.train_step_work(d, rows=1, seq=4, rank=2,
                                        targets=("wq", "wv"))
    blocks = 2 * 24 * 14_909_440 * 4               # 2 N per token
    head = 2 * 896 * 151_936 * 3                   # 3 predicted positions
    attn = 24 * 2 * 2 * 896 * (4 * 5 // 2)         # 10 causal pairs
    lora = 2 * 24 * 2 * ((896 + 896) + (896 + 128)) * 4
    assert ops == (blocks + head + attn + lora) + (blocks + head + 2 * attn + 2 * lora)
    lora_params = 24 * 2 * (1_792 + 1_024)
    assert nbytes == 2 * d.weight_bytes + 7 * 4 * lora_params


def test_qwen2p5_14b_block_matches_published_split():
    c = {"num_hidden_layers": 8, "hidden_size": 5120,
         "num_attention_heads": 40, "num_key_value_heads": 8,
         "intermediate_size": 13824, "vocab_size": 152064,
         "tie_word_embeddings": False}
    d = qwen2.Dims.from_config(c)
    attn = 2 * 5120 * 5120 + 2 * 5120 * 1024       # 62.9 M
    mlp = 3 * 5120 * 13824                         # 212.3 M
    assert d.block_matmul_params == attn + mlp == 275_251_200


def test_least_time_names_its_bound():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_time(1000.0, 50.0, peaks) == (10.0, "compute")
    assert flops.least_time(100.0, 50.0, peaks) == (5.0, "memory")
    assert flops.least_time(100.0, 50.0, peaks)[0] == pytest.approx(5.0)
