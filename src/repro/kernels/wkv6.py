"""RWKV6 WKV recurrence kernel.

The recurrence is sequential in time but embarrassingly parallel over
(batch × head).  Grid: (B·H, S/chunk) with the chunk axis sequential — the
(hd × hd) WKV state lives in VMEM scratch and persists across sequential
grid steps; inside a chunk, a fori_loop loads one aligned sublane tile of
tokens (8 rows in fp32, 16 in bf16) and advances them one at a time with
rank-1 outer-product updates (VPU work: hd=64 → 64×64 tiles).

This is the TPU re-blocking of the original CUDA wkv kernel: instead of one
thread-block per (b,h) with warp-level state in registers, we keep the state
resident in VMEM and stream r/k/v/w chunks HBM→VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, state_scr, *,
            chunk: int, rows: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    hd = state_scr.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 1))

    def col(row):
        # (1, hd) -> (hd, 1) through a masked lane reduction: the chip has
        # no cheap relayout of a lone row into a column
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    u_c = col(u_ref[0].astype(jnp.float32))           # (hd, 1)

    def tile(i, state):
        # loads and stores move whole (rows, hd) sublane tiles at aligned
        # offsets; the tokens inside a tile are stepped in straight-line code
        base = pl.multiple_of(i * rows, rows)
        sl = (0, pl.ds(base, rows), slice(None))
        rt, kt, vt, wt = (ref[sl].astype(jnp.float32)
                          for ref in (r_ref, k_ref, v_ref, w_ref))
        ys = []
        for j in range(rows):
            k_c = col(kt[j:j + 1])
            kv = k_c * vt[j:j + 1]                    # (hd, hd) outer product
            ys.append(jnp.sum(col(rt[j:j + 1]) * (state + u_c * kv),
                              axis=0, keepdims=True))  # (1, hd)
            state = jnp.exp(col(wt[j:j + 1])) * state + kv
        o_ref[sl] = jnp.concatenate(ys, axis=0).astype(o_ref.dtype)
        return state

    state_scr[...] = jax.lax.fori_loop(0, chunk // rows, tile, state_scr[...])


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_kernel(r, k, v, w, u, chunk: int = 256, interpret: bool = False):
    """r,k,v,w: (B,S,H,hd); u: (H,hd). Returns fp32 (B,S,H,hd)."""
    B, S, H, hd = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    # one packed sublane tile of the narrowest input dtype
    rows = 32 // min(t.dtype.itemsize for t in (r, k, v, w))
    assert chunk % rows == 0, (chunk, rows)

    def flat(t):
        return t.transpose(0, 2, 1, 3).reshape(B * H, S, hd)

    rf, kf, vf, wf = flat(r), flat(k), flat(v), flat(w)
    # (H, 1, hd): a (1, 1, hd) block spans the last two dims, which the
    # chip's tiling requires; bh % H picks the head without tiling u over B
    u3 = u.reshape(H, 1, hd)

    out = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, rows=rows),
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, hd), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, hd), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, hd), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, 1, hd), lambda bh, ic: (bh % H, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, hd), lambda bh, ic: (bh, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(rf, kf, vf, wf, u3)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
