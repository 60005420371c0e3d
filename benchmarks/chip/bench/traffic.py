"""General generators of the benchmark's inputs, driven by a traffic file.

Document lengths are drawn by stratified quantiles and shuffled by the
seed, so every seed gives the same set of lengths in another order.
"""
from __future__ import annotations

import json
import math
import os
from statistics import NormalDist
import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BOS, SEP, EOS = 1, 2, 3
SPECIAL = 4


def load_traffic(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def lognormal_sizes(n: int, median: float, sigma: float, lo: int, hi: int,
                    rng: np.random.Generator) -> np.ndarray:
    """``n`` lognormal sizes at the stratified quantiles (i + 0.5) / n,
    clipped to [lo, hi], in an order drawn from ``rng``."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    sizes = np.clip(np.round(median * np.exp(sigma * z)), lo, hi).astype(int)
    return sizes[rng.permutation(n)]


# -- packed instruction documents (client training) --------------------------


def _document(rng, task: int, n: int, vocab: int):
    """One instruction document of ``n`` tokens: BOS, instruction, SEP,
    response, EOS; the response is a task-specific function of the
    instruction, so the task is learnable.  Returns (tokens, loss mask on
    the response and EOS targets)."""
    m = max(1, (n - 3) // 2)
    instr = rng.integers(SPECIAL, vocab, m)
    resp = (instr * (1 + 2 * (task % 7)) + 3 + 11 * task) % (vocab - SPECIAL) + SPECIAL
    toks = np.concatenate([[BOS], instr, [SEP], resp, [EOS]]).astype(np.int32)
    mask = np.zeros(len(toks), np.float32)
    mask[2 + m:] = 1.0
    return toks, mask


def packed_rows(rng, rows: int, seq: int, vocab: int, task_probs,
                median: float, sigma: float, lo: int, hi: int):
    """``rows`` sequences of ``seq`` tokens, each packed with whole
    documents of lognormal length (the last one cut at the row's end), tasks
    drawn from ``task_probs``."""
    n_docs = rows * int(math.ceil(2 * seq / median)) + 8
    lengths = lognormal_sizes(n_docs, median, sigma, lo, hi, rng)
    tasks = rng.choice(len(task_probs), size=n_docs, p=task_probs)
    toks = np.zeros((rows, seq), np.int32)
    mask = np.zeros((rows, seq), np.float32)
    d = 0
    for r in range(rows):
        pos = 0
        while pos < seq:
            t, m = _document(rng, int(tasks[d % n_docs]),
                             int(lengths[d % n_docs]), vocab)
            d += 1
            k = min(len(t), seq - pos)
            toks[r, pos:pos + k] = t[:k]
            mask[r, pos:pos + k] = m[:k]
            pos += k
    return toks, mask


def client_corpus(t: dict, vocab: int, seed: int):
    """Per-client packed training rows with a Dirichlet task mix, and the
    held-out eval rows (uniform task mix)."""
    rng = np.random.default_rng([seed, 11])
    n_clients = sum(c for _, c in t["clients"])
    mix = rng.dirichlet([t["dirichlet_alpha"]] * t["tasks"], size=n_clients)
    clients = []
    for k in range(n_clients):
        clients.append(packed_rows(
            rng, t["rows_per_client"], t["seq_len"], vocab, mix[k],
            t["doc_len_median"], t["doc_len_sigma"], t["doc_len_min"],
            t["doc_len_max"]))
    uniform = np.full(t["tasks"], 1.0 / t["tasks"])
    ev = packed_rows(rng, t["eval_rows"], t["eval_seq_len"], vocab, uniform,
                     t["doc_len_median"], t["doc_len_sigma"],
                     t["doc_len_min"], t["doc_len_max"])
    return clients, ev
