"""Flash-decoding kernel over the MLA compressed-latent ring cache.

The absorbed-MLA decode attends the ``(B, cap, kvr)`` latent stream
directly (DeepSeek-V3 weight absorption): the effective key of slot ``s``
is ``[c_kv | k_rope]`` and its value is ``c_kv`` itself, shared by every
query head (MQA over the latent).  Queries arrive already absorbed:
``q_eff = [q_nope · W_k | q_rope]`` of shape ``(B, C, H, kvr + rope)``.

Same streaming contract as :mod:`repro.kernels.ring_decode` — the ring
residency ∧ causal ∧ window mask is computed in-kernel from the ``(B,)``
``pos``/``length`` scalars, the latent cache is consumed in ``bk``-slot
blocks with online softmax, and int8 caches are dequantized per block with
their *separate* per-token scales for the ``c_kv`` and ``k_rope`` halves
(a single concatenated scale would be wrong: absmax is taken per half).

Grid: (B·H, cap/bk), KV axis innermost; scratch persists across it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ring_decode import (NEG_INF, flush_flash_scratch,
                                       online_softmax_step,
                                       reset_flash_scratch, ring_mask_tile)


def _kernel(*refs, scale: float, bk: int, nk: int, cap: int, window: int,
            quantized: bool, n_heads: int):
    if quantized:
        (pos_ref, len_ref, n_ref, q_ref, ckv_ref, kr_ref, s1_ref, s2_ref,
         o_ref, m_scr, l_scr, acc_scr) = refs
    else:
        (pos_ref, len_ref, n_ref, q_ref, ckv_ref, kr_ref,
         o_ref, m_scr, l_scr, acc_scr) = refs
    b = pl.program_id(0) // n_heads
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        reset_flash_scratch(m_scr, l_scr, acc_scr)

    q = q_ref[0].astype(jnp.float32)                  # (C, kvr + rope)
    ckv = ckv_ref[0].astype(jnp.float32)              # (bk, kvr)
    kr = kr_ref[0].astype(jnp.float32)                # (bk, rope)
    if quantized:
        ckv = ckv * s1_ref[0]                         # per-half absmax scales
        kr = kr * s2_ref[0]
    k = jnp.concatenate([ckv, kr], axis=-1)           # (bk, kvr + rope)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (C, bk)

    mask = ring_mask_tile(pos_ref[b], len_ref[b], n_ref[b], ik,
                          bk=bk, cap=cap, C=q.shape[0], window=window)
    s = jnp.where(mask, s, NEG_INF)
    online_softmax_step(s, ckv, m_scr, l_scr, acc_scr)  # value = latent

    @pl.when(ik == nk - 1)
    def _flush():
        flush_flash_scratch(o_ref, m_scr, l_scr, acc_scr)


@functools.partial(jax.jit, static_argnames=("cap", "scale", "window", "bk",
                                             "interpret"))
def mla_ring_decode_kernel(q_eff, c_kv, k_rope, pos, length, n_tokens,
                           cap: int, scale: float,
                           c_kv_scale=None, k_rope_scale=None,
                           window: int = 0, bk: int = 128,
                           interpret: bool = False):
    """q_eff: (B,C,H,kvr+rope); c_kv: (B,capp,kvr), k_rope: (B,capp,rope)
    (capp = cap padded to a bk multiple); pos/length/n_tokens: (B,) ring
    state AFTER the chunk write; *_scale: (B,capp,1) when int8.  ``scale``
    is the softmax scale of the UN-absorbed head dim (1/√(nope+rope) — not
    derivable from q_eff's width).  Returns out_lat (B,C,H,kvr) fp32 — the
    caller applies the absorbed V-projection."""
    B, C, H, dq = q_eff.shape
    capp, kvr = c_kv.shape[1], c_kv.shape[2]
    assert capp % bk == 0, (capp, bk)
    nk = capp // bk
    quantized = c_kv_scale is not None

    qf = q_eff.transpose(0, 2, 1, 3).reshape(B * H, C, dq)
    # (B,) ring scalars through scalar prefetch (see ring_decode)
    scal = [x.astype(jnp.int32) for x in (pos, length, n_tokens)]

    def q_index(bh, ik_, *_):
        return (bh, 0, 0)

    def kv_index(bh, ik_, *_):
        return (bh // H, ik_, 0)

    in_specs = [
        pl.BlockSpec((1, C, dq), q_index),
        pl.BlockSpec((1, bk, kvr), kv_index),
        pl.BlockSpec((1, bk, dq - kvr), kv_index),
    ]
    args = [qf, c_kv, k_rope]
    if quantized:
        in_specs += [pl.BlockSpec((1, bk, 1), kv_index)] * 2
        args += [c_kv_scale, k_rope_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B * H, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, C, kvr), q_index),
        scratch_shapes=[
            pltpu.VMEM((C, 1), jnp.float32),
            pltpu.VMEM((C, 1), jnp.float32),
            pltpu.VMEM((C, kvr), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bk=bk, nk=nk, cap=cap,
                          window=window, quantized=quantized, n_heads=H),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, C, kvr), jnp.float32),
        interpret=interpret,
    )(*scal, *args)
    return out.reshape(B, H, C, kvr).transpose(0, 2, 1, 3)
