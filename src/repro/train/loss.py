"""Losses. Chunked cross-entropy: logits are materialized only for a
sequence chunk at a time (scan), bounding peak memory to
(B, chunk, vocab) instead of (B, S, vocab) — essential for the 150k-vocab
archs at seq 4096 on 16 GB chips.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.models import transformer as T

#: fp32 logits one loss chunk may hold (rows × chunk × vocab × 4 bytes)
LOGITS_BUDGET_BYTES = 512 * 2**20


def bounded_loss_chunk(rows: int, seq_len: int, vocab: int,
                       budget: int = LOGITS_BUDGET_BYTES) -> int:
    """The longest sequence chunk, at most ``seq_len``, whose fp32 logits
    for ``rows`` sequences fit ``budget`` bytes.  A 128-row eval batch at a
    152k vocabulary costs 78 MB of logits per position, so one chunk of a
    whole 512-token sequence would need 40 GB."""
    return max(1, min(seq_len, budget // (4 * rows * vocab)))


def _ce_chunk(head: jnp.ndarray, hidden, targets, mask):
    """hidden: (B,c,d), targets: (B,c), mask: (B,c). Returns (sum_loss, sum_cnt, sum_correct)."""
    logits = (hidden @ head).astype(jnp.float32)           # (B,c,V)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (lse - tgt) * mask
    correct = (jnp.argmax(logits, -1) == targets).astype(jnp.float32) * mask
    return nll.sum(), mask.sum(), correct.sum()


def chunked_ce(cfg: ModelConfig, params, hidden: jnp.ndarray, tokens: jnp.ndarray,
               loss_mask: jnp.ndarray, chunk: int = 512) -> Tuple[jnp.ndarray, Dict]:
    """Next-token CE over `tokens`, masked by `loss_mask` on *target*
    positions. hidden: (B,S,d) aligned with tokens (B,S)."""
    from repro.common import flags
    if flags.scan_unroll():
        chunk = max(chunk, (tokens.shape[1] - 1) // 2)   # analysis lowering
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    B, S, d = hidden.shape
    # predict token t+1 from hidden t
    h = hidden[:, :-1]
    tgt = tokens[:, 1:]
    msk = loss_mask[:, 1:]
    Sm = h.shape[1]
    c = min(chunk, Sm)
    nc = Sm // c
    rem = Sm - nc * c

    # remat: logits for a chunk are recomputed in backward instead of living
    # across the whole loss scan (8 × (B,c,V) fp32 otherwise)
    ce_chunk = jax.checkpoint(_ce_chunk,
                              policy=jax.checkpoint_policies.nothing_saveable)

    def body(carry, xs):
        s_l, n_l, a_l = carry
        hh, tt, mm = xs
        s, n, a = ce_chunk(head, hh, tt, mm)
        return (s_l + s, n_l + n, a_l + a), None

    from repro.common import flags
    xs = (h[:, : nc * c].reshape(B, nc, c, d).swapaxes(0, 1),
          tgt[:, : nc * c].reshape(B, nc, c).swapaxes(0, 1),
          msk[:, : nc * c].reshape(B, nc, c).swapaxes(0, 1))
    (s, n, acc), _ = jax.lax.scan(body, (0.0, 0.0, 0.0), xs,
                                  unroll=flags.scan_unroll())
    if rem:
        s2, n2, a2 = _ce_chunk(head, h[:, nc * c:], tgt[:, nc * c:], msk[:, nc * c:])
        s, n, acc = s + s2, n + n2, acc + a2
    n = jnp.maximum(n, 1.0)
    return s / n, {"loss": s / n, "tokens": n, "accuracy": acc / n}
