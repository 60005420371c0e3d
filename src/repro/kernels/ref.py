"""Pure-jnp oracles for every Pallas kernel (ground truth for allclose
tests and the CPU execution path)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def lora_matmul_ref(x, w, a, b, scale):
    """y = x @ w + scale * (x @ aᵀ) @ bᵀ.
    x: (M, din), w: (din, dout), a: (r, din), b: (dout, r)."""
    y = x @ w
    z = x @ a.T.astype(x.dtype)
    return y + (z @ b.T.astype(x.dtype)) * scale


def flash_attention_ref(q, k, v, causal: bool = True, window: int = 0):
    """q: (B,S,H,hd), k/v: (B,T,K,hd) grouped-query attention, fp32 softmax."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    g = H // K
    qf = q.astype(jnp.float32).reshape(B, S, K, g, hd)
    s = jnp.einsum("bskgh,btkh->bkgst", qf, k.astype(jnp.float32))
    s = s * (1.0 / math.sqrt(hd))
    if causal or window:        # window applies independently of causal,
        qpos = jnp.arange(S)[:, None]   # matching the kernel's mask
        kpos = jnp.arange(T)[None, :]
        m = jnp.ones((S, T), jnp.bool_)
        if causal:
            m &= kpos <= qpos
        if window:
            m &= kpos > (qpos - window)
        s = jnp.where(m[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkh->bskgh", p, v.astype(jnp.float32))
    return o.reshape(B, S, H, hd)


def _ring_mask(pos, length, cap, qpos, window):
    from repro.models.attention_core import ring_attend_mask
    return ring_attend_mask(pos, length, cap, qpos, window)


def ring_decode_ref(q, k, v, pos, length, n_tokens, window: int = 0,
                    k_scale=None, v_scale=None):
    """Dense decode-attention oracle over a GQA ring cache.

    q: (B,C,H,hd); k/v: (B,cap,K,hd) raw cache storage (int8 with
    (B,cap,K,1) scales supported — dequantized WHOLE, in fp32);
    pos/length/n_tokens: (B,) ring state AFTER the chunk write.  This is
    the O(cap)-live-memory math the streamed/kernel paths are tested
    against: full (B,H,C,cap) scores + dense (B,C,cap) ring mask.
    """
    B, C, H, hd = q.shape
    cap, K = k.shape[1], k.shape[2]
    g = H // K
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if k_scale is not None:
        kf = kf * k_scale
        vf = vf * v_scale
    qf = q.astype(jnp.float32).reshape(B, C, K, g, hd)
    s = jnp.einsum("bckgh,btkh->bkgct", qf, kf) / math.sqrt(hd)
    qpos = (pos - n_tokens)[:, None] + jnp.arange(C)[None, :]
    mask = _ring_mask(pos, length, cap, qpos, window)        # (B,C,cap)
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgct,btkh->bckgh", p, vf)
    return o.reshape(B, C, H, hd)


def mla_ring_decode_ref(q_eff, c_kv, k_rope, pos, length, n_tokens,
                        scale: float, window: int = 0,
                        c_kv_scale=None, k_rope_scale=None):
    """Dense absorbed-MLA decode oracle over the compressed-latent ring
    cache.  q_eff: (B,C,H,kvr+rope); c_kv: (B,cap,kvr); k_rope:
    (B,cap,rope); returns out_lat (B,C,H,kvr) fp32."""
    B, C, H, _ = q_eff.shape
    cap = c_kv.shape[1]
    ckv = c_kv.astype(jnp.float32)
    kr = k_rope.astype(jnp.float32)
    if c_kv_scale is not None:
        ckv = ckv * c_kv_scale
        kr = kr * k_rope_scale
    keff = jnp.concatenate([ckv, kr], axis=-1)
    s = jnp.einsum("bchd,btd->bhct", q_eff.astype(jnp.float32), keff) * scale
    qpos = (pos - n_tokens)[:, None] + jnp.arange(C)[None, :]
    mask = _ring_mask(pos, length, cap, qpos, window)
    s = jnp.where(mask[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhct,btk->bchk", p, ckv)


def wkv6_ref(r, k, v, w, u):
    """RWKV6 recurrence (see repro.models.rwkv.wkv_scan).
    r,k,v,w: (B,S,H,hd) with w = log-decay (<0); u: (H,hd). fp32 out."""
    B, S, H, hd = r.shape
    rf, kf, vf, wf = (t.astype(jnp.float32) for t in (r, k, v, w))

    def step(state, inp):
        rt, kt, vt, wt = inp
        kv = kt[..., :, None] * vt[..., None, :]
        y = jnp.einsum("bhk,bhkv->bhv", rt, state + u[..., None] * kv)
        state = jnp.exp(wt)[..., None] * state + kv
        return state, y

    s0 = jnp.zeros((B, H, hd, hd), jnp.float32)
    xs = tuple(t.swapaxes(0, 1) for t in (rf, kf, vf, wf))
    _, ys = jax.lax.scan(step, s0, xs)
    return ys.swapaxes(0, 1)


def adapter_gram_ref(x):
    """Gram matrix xᵀ x in fp32. x: (m, r)."""
    xf = x.astype(jnp.float32)
    return xf.T @ xf


def bgmv_ref(x, a_pages, b_pages, table, rank, scale, ids):
    """Multi-tenant paged LoRA delta oracle: row b applies adapter
    ``ids[b]`` — its pages ``table[ids[b]]``, lanes below its rank, its
    scale.  x: (B,C,din); a_pages: (P,pr,din); b_pages: (P,dout,pr).
    Page slot j's lane ℓ is global lane j·pr + ℓ.  Returns (B,C,dout) fp32."""
    B = x.shape[0]
    tbl = table[ids]                                       # (B, Pmax)
    A = a_pages[tbl].astype(jnp.float32)                   # (B,Pmax,pr,din)
    A = A.reshape(B, -1, A.shape[-1])                      # (B, R, din)
    Bp = jnp.moveaxis(b_pages[tbl].astype(jnp.float32), 2, 1)
    Bp = Bp.reshape(B, Bp.shape[1], -1)                    # (B, dout, R)
    keep = jnp.arange(A.shape[1])[None, :] < rank[ids][:, None]
    z = jnp.einsum("bcd,brd->bcr", x.astype(jnp.float32), A)
    z = jnp.where(keep[:, None, :], z, 0.0)
    return (jnp.einsum("bcr,bor->bco", z, Bp)
            * scale.astype(jnp.float32)[ids][:, None, None])
