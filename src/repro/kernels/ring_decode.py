"""Flash-decoding kernel over a per-slot ring-buffer KV cache (GQA).

Decode-side attention for the serving hot loop: queries are a token chunk
(C = 1..prefill_chunk) attending a ``(B, cap, K, hd)`` ring cache whose
per-row state is just ``pos``/``length`` of shape ``(B,)``.  The dense path
materializes a ``(B, H, C, cap)`` score tensor and a ``(B, C, cap)`` bool
mask per step; this kernel streams the cache in ``bk``-slot key blocks with
online softmax, so live memory is O(C·bk) score tiles — the split-K
("flash-decoding") regime where ``cap`` ≫ ``C``.

The ring mask is computed *inside* the kernel from slot indices (the math of
:func:`repro.models.attention_core.ring_slot_positions`): slot ``s`` holds
absolute position ``p_abs = last - (last - s) mod cap`` and is attendable
iff it is resident (``p_abs >= pos - length``), causally visible
(``p_abs <= qpos``), inside the sliding window when one is set, and a real
slot (``s < cap`` — block padding).  Query positions come from the same
scalars: ``qpos = pos - n_tokens + t`` (``pos`` is the ring state AFTER the
chunk write), so ragged ``n_tokens`` chunks mask correctly per row.

int8 caches are dequantized **per key block** inside the kernel (per-token
absmax scales ride along as a second operand) — no full-precision cache
copy is ever formed in HBM.

Grid: (B·H, cap/bk) with the KV axis innermost/sequential; running
max / normalizer / accumulator persist in VMEM scratch.  GQA KV blocks are
addressed by index_map arithmetic (kv head = q head // group) so the cache
is streamed once per group, never repeated.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def ring_mask_tile(pos, length, n, ik, *, bk: int, cap: int, C: int,
                   window: int):
    """(C, bk) residency ∧ causal ∧ window mask for kv block ``ik`` of one
    batch row, from its ring scalars — the in-kernel form of
    :func:`repro.models.attention_core.ring_block_mask` (shared by the GQA
    and MLA decode kernels)."""
    s_idx = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (C, bk), 1)
    last = pos - 1
    p_abs = last - jnp.mod(last - s_idx, cap)         # slot -> absolute pos
    qpos = pos - n + jax.lax.broadcasted_iota(jnp.int32, (C, bk), 0)
    mask = (p_abs >= pos - length) & (s_idx < cap) & (p_abs <= qpos)
    if window:
        mask &= p_abs > (qpos - window)
    return mask


def reset_flash_scratch(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def online_softmax_step(s, v, m_scr, l_scr, acc_scr):
    """Fold one masked (C, bk) score tile + its (bk, dv) values into the
    running max / normalizer / accumulator VMEM scratch."""
    m_prev = m_scr[...]                               # (C, 1)
    m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_cur


def flush_flash_scratch(o_ref, m_scr, l_scr, acc_scr):
    del m_scr
    o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                ).astype(o_ref.dtype)


def _kernel(*refs, scale: float, bk: int, nk: int, cap: int, window: int,
            quantized: bool, n_heads: int):
    if quantized:
        (pos_ref, len_ref, n_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
         o_ref, m_scr, l_scr, acc_scr) = refs
    else:
        (pos_ref, len_ref, n_ref, q_ref, k_ref, v_ref,
         o_ref, m_scr, l_scr, acc_scr) = refs
    b = pl.program_id(0) // n_heads
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        reset_flash_scratch(m_scr, l_scr, acc_scr)

    q = q_ref[0].astype(jnp.float32)                  # (C, hd)
    k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    if quantized:
        k = k * ks_ref[0]                             # (bk, 1) per-token scale
        v = v * vs_ref[0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (C, bk)

    mask = ring_mask_tile(pos_ref[b], len_ref[b], n_ref[b], ik,
                          bk=bk, cap=cap, C=q.shape[0], window=window)
    s = jnp.where(mask, s, NEG_INF)
    online_softmax_step(s, v, m_scr, l_scr, acc_scr)

    @pl.when(ik == nk - 1)
    def _flush():
        flush_flash_scratch(o_ref, m_scr, l_scr, acc_scr)


@functools.partial(jax.jit, static_argnames=("cap", "window", "bk", "interpret"))
def ring_decode_kernel(q, k, v, pos, length, n_tokens, cap: int,
                       k_scale=None, v_scale=None, window: int = 0,
                       bk: int = 128, interpret: bool = False):
    """q: (B,C,H,hd); k/v: (B,capp,K,hd) ring caches (capp = cap padded to a
    bk multiple); pos/length/n_tokens: (B,) ring state AFTER the chunk
    write; k_scale/v_scale: (B,capp,K,1) per-token absmax scales when the
    cache is int8.  Returns (B,C,H,hd) fp32."""
    B, C, H, hd = q.shape
    capp, K = k.shape[1], k.shape[2]
    g = H // K
    assert capp % bk == 0, (capp, bk)
    nk = capp // bk
    scale = 1.0 / (hd ** 0.5)
    quantized = k_scale is not None

    qf = q.transpose(0, 2, 1, 3).reshape(B * H, C, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * K, capp, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * K, capp, hd)
    # the (B,) ring scalars ride in SMEM via scalar prefetch: a (1,) block
    # of a (B,) array is not a legal VMEM/SMEM tile on the chip
    scal = [x.astype(jnp.int32) for x in (pos, length, n_tokens)]

    def q_index(bh, ik_, *_):
        return (bh, 0, 0)

    def kv_index(bh, ik_, *_):
        return (bh // H * K + (bh % H) // g, ik_, 0)

    in_specs = [
        pl.BlockSpec((1, C, hd), q_index),
        pl.BlockSpec((1, bk, hd), kv_index),
        pl.BlockSpec((1, bk, hd), kv_index),
    ]
    args = [qf, kf, vf]
    if quantized:
        in_specs += [pl.BlockSpec((1, bk, 1), kv_index)] * 2
        args += [k_scale.transpose(0, 2, 1, 3).reshape(B * K, capp, 1),
                 v_scale.transpose(0, 2, 1, 3).reshape(B * K, capp, 1)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B * H, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, C, hd), q_index),
        scratch_shapes=[
            pltpu.VMEM((C, 1), jnp.float32),    # running max
            pltpu.VMEM((C, 1), jnp.float32),    # running normalizer
            pltpu.VMEM((C, hd), jnp.float32),   # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bk=bk, nk=nk, cap=cap,
                          window=window, quantized=quantized, n_heads=H),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, C, hd), jnp.float32),
        interpret=interpret,
    )(*scal, *args)
    return out.reshape(B, H, C, hd).transpose(0, 2, 1, 3)
