"""Plain reference of the Qwen2 decoder (arXiv:2407.10671) with LoRA, its
loss, a client's AdamW steps and the FLoRIST server, for the comparisons
that decide ``correct``.

Straightforward ``jax.numpy`` in float32 under ``default_matmul_precision
("highest")``: no kernels, no cache, no batching tricks.  It imports nothing
of the program under test and reads only the benchmark's own weights
(``bench.weights`` layout) and inputs.

Block (as published): RMSNorm -> q/k/v projections with bias -> rotary
embedding (halves rotated, base ``rope_theta``) -> grouped-query causal
attention scaled by 1/sqrt(head_dim) -> output projection -> residual;
RMSNorm -> SiLU-gated MLP -> residual; final RMSNorm; head (the transposed
embedding when tied).  LoRA on a projection ``W (d_in, d_out)`` adds
``scale * (x A^T) B^T`` with ``A (r, d_in)``, ``B (d_out, r)``.

``precision="fp8"`` is the control: every matmul operand is rounded to
float8 e4m3 with a per-tensor scale (the next precision below the bf16
the configuration states), the rest as above.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
TARGETS = ("wq", "wk", "wv", "wo")
FP8_MAX = 448.0


def _q(x, precision: str):
    x = x.astype(F32)
    if precision == "fp8":
        # per-tensor scale to the format's range, rounding in the forward
        # pass, gradients straight through (the rounding is the only change)
        s = jax.lax.stop_gradient(
            FP8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30))
        r = (x * s).astype(jnp.float8_e4m3fn).astype(F32) / s
        x = x + jax.lax.stop_gradient(r - x)
    return x


def _mm(x, w, precision):
    return _q(x, precision) @ _q(w, precision)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(F32)


def _rope(x, theta):
    S, hd = x.shape[-3], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _proj(x, w, b, lora, name, precision):
    y = _mm(x, w, precision)
    if b is not None:
        y = y + b.astype(F32)
    if lora is not None and name in lora:
        A, B, s = lora[name]
        y = y + _mm(_mm(x, A.T, precision), B.T, precision) * s
    return y


def _layer(c, x, p, lora, precision):
    """One decoder layer over a (S, d) sequence."""
    S = x.shape[0]
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["hidden_size"] // H
    eps = c["rms_norm_eps"]
    a = p["attn"]
    h = _rms(x, p["ln1"], eps)
    q = _proj(h, a["wq"], a.get("bq"), lora, "wq", precision).reshape(S, H, hd)
    k = _proj(h, a["wk"], a.get("bk"), lora, "wk", precision).reshape(S, K, hd)
    v = _proj(h, a["wv"], a.get("bv"), lora, "wv", precision).reshape(S, K, hd)
    q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    g = H // K
    qg = q.reshape(S, K, g, hd)
    sc = jnp.einsum("skgh,tkh->kgst", _q(qg, precision), _q(k, precision))
    sc = sc / np.sqrt(hd)
    mask = jnp.tril(jnp.ones((S, S), bool))
    sc = jnp.where(mask, sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("kgst,tkh->skgh", _q(pr, precision), _q(v, precision))
    o = o.reshape(S, H * hd)
    x = x + _proj(o, a["wo"], None, lora, "wo", precision)
    m = p["mlp"]
    h = _rms(x, p["ln2"], eps)
    gt = _mm(h, m["w_gate"], precision)
    up = _mm(h, m["w_up"], precision)
    return x + _mm(jax.nn.silu(gt) * up, m["w_down"], precision)


def hidden(c, w, tokens, lora=None, precision="float32"):
    """Final-norm hidden states (S, d) of one sequence of token ids (S,)."""
    blocks = w["blocks"][0]
    x = w["embed"][tokens].astype(F32)

    def body(x, xs):
        p, la = xs
        return jax.checkpoint(lambda x_: _layer(c, x_, p, la, precision))(x), None

    x, _ = jax.lax.scan(body, x, (blocks, lora))
    return _rms(x, w["final_norm"], c["rms_norm_eps"])


def head(c, w):
    return w["embed"].T if c.get("tie_word_embeddings") else w["lm_head"]


def logits(c, w, tokens, lora=None, precision="float32"):
    return _mm(hidden(c, w, tokens, lora, precision), head(c, w), precision)


def nll_sum(c, w, lora, tokens, mask, precision="float32"):
    """(sum of next-token NLL over masked targets, number of targets) over
    rows of token ids (R, S): position t predicts token t+1 where
    ``mask[t+1]`` is set."""
    def one(t, m):
        lg = logits(c, w, t, lora, precision)[:-1]
        lse = jax.nn.logsumexp(lg, -1)
        tgt = jnp.take_along_axis(lg, t[1:, None], -1)[:, 0]
        mm = m[1:].astype(F32)
        return jnp.sum((lse - tgt) * mm), jnp.sum(mm)

    s, n = jax.vmap(one)(tokens, mask)
    return jnp.sum(s), jnp.sum(n)


@functools.lru_cache(maxsize=None)
def _nll_grad(c_items, precision):
    c = dict(c_items)

    def fn(w, lora, t, m):
        def f(la):
            return nll_sum(c, w, la, t, m, precision)
        (s, n), g = jax.value_and_grad(f, has_aux=True)(lora)
        return s, n, g
    return jax.jit(fn)


def _key(c):
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str, bool))))


# -- a client's local steps ---------------------------------------------------


def batch_loss_and_grad(c, w, lora, tokens, mask, precision="float32"):
    """Mean masked NLL over a batch (rows of ``tokens``) and its gradient
    with respect to the LoRA factors (the loss is sum(nll) / sum(mask) over
    the whole batch)."""
    with jax.default_matmul_precision("highest"):
        s, n, g = _nll_grad(_key(c), precision)(w, lora, jnp.asarray(tokens),
                                                jnp.asarray(mask))
    n = jnp.maximum(n, 1.0)
    return s / n, jax.tree.map(lambda x: x / n, g)


def adamw_steps(c, w, lora, batches, opt: Dict, precision="float32"):
    """AdamW (global-norm clipping, bias correction, constant rate) over
    ``batches``; only A and B of each target train, the scale is fixed.
    Returns (final lora, losses, first-step gradients as the optimizer
    gets them, per-step gradients)."""
    b1, b2 = opt["betas"]
    params = {t: (A, B) for t, (A, B, _) in lora.items()}
    scales = {t: s for t, (_, _, s) in lora.items()}
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, grads_seen = [], []
    for i, (tok, msk) in enumerate(batches, start=1):
        full = {t: (A, B, scales[t]) for t, (A, B) in params.items()}
        loss, g = batch_loss_and_grad(c, w, full, tok, msk, precision)
        g = {t: (gA, gB) for t, (gA, gB, _) in g.items()}
        if opt["grad_clip"]:
            norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
            f = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(norm, 1e-9))
            g = jax.tree.map(lambda x: x * f, g)
        grads_seen.append(g)
        losses.append(float(loss))
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
        params = jax.tree.map(
            lambda p, m, v: p - opt["lr"] * (
                (m / (1 - b1 ** i)) / (jnp.sqrt(v / (1 - b2 ** i)) + opt["eps"])
                + opt["weight_decay"] * p), params, mu, nu)
    final = {t: (A, B, scales[t]) for t, (A, B) in params.items()}
    return final, losses, grads_seen


# -- the FLoRIST server -------------------------------------------------------


def energy_rank(s: np.ndarray, tau: float) -> int:
    e = np.cumsum(np.asarray(s, np.float64) ** 2)
    return int(min(np.searchsorted(e / e[-1], tau, side="left") + 1, len(s)))


def florist(clients: Sequence[Dict], weights: Sequence[float], tau: float):
    """Per target and layer: the weighted sum of the clients' updates
    dW = sum_k w_k s_k B_k A_k, its singular values, and the energy-kept
    rank.  Returns {target: (dW (L, dout, din), spectra (L, R), ranks)}."""
    out = {}
    with jax.default_matmul_precision("highest"):
        for t in clients[0]:
            dw = sum(wk * jnp.einsum("lor,lri->loi", B * s[:, None, None], A)
                     for wk, cl in zip(weights, clients)
                     for A, B, s in [cl[t]])
            u, sv, vt = jnp.linalg.svd(dw, full_matrices=False)
            sv_h = np.asarray(sv)
            ranks = [energy_rank(x, tau) for x in sv_h]
            out[t] = (dw, (u, sv, vt), sv_h, ranks)
    return out


def truncated(svd, l: int, p: int):
    """The rank-p update U_p S_p V_p^T of layer l."""
    u, s, vt = svd
    return (u[l, :, :p] * s[l, :p]) @ vt[l, :p]
