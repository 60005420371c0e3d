"""Jit'd public wrappers around the Pallas kernels.

On a TPU backend the kernels compile natively.  On any other backend they
run in Pallas ``interpret=True`` mode, which is a rule for the CPU tests
only: it checks a kernel's math, not whether the TPU compiler accepts its
blocks (``tests/test_tpu_compile.py`` does that), and its timings say
nothing about the chip.  Shape padding to block multiples is handled here
so callers can use arbitrary sizes.  ``lora_matmul`` carries a ``custom_vjp`` (backward via the
reference math) so ``use_kernels=True`` training differentiates through the
fused forward.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.adapter_gram import adapter_gram_kernel
from repro.kernels.bgmv import bgmv_kernel
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.lora_matmul import lora_matmul_kernel
from repro.kernels.mla_ring_decode import mla_ring_decode_kernel
from repro.kernels.ring_decode import ring_decode_kernel
from repro.kernels.wkv6 import wkv6_kernel


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x, axis: int, mult: int):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


def _lora_matmul_fwd(x, w, a, b, scale, bm, bn):
    dout = w.shape[1]
    xf, M = _pad_to(x, 0, bm)
    b_scaled = (b * scale).astype(w.dtype)
    wp, _ = _pad_to(w, 1, bn)
    bp, _ = _pad_to(b_scaled, 0, bn)
    y = lora_matmul_kernel(xf, wp, a.astype(x.dtype), bp.astype(x.dtype),
                           bm=bm, bn=bn, interpret=_interpret())
    return y[:M, :dout]


@functools.lru_cache(maxsize=None)
def _lora_matmul_vjp(bm: int, bn: int):
    """custom_vjp-wrapped fused LoRA matmul: forward runs the Pallas kernel,
    backward is the reference math (Pallas kernels have no autodiff rule, so
    without this the ``use_kernels=True`` train step cannot differentiate)."""

    @jax.custom_vjp
    def f(x, w, a, b, scale):
        return _lora_matmul_fwd(x, w, a, b, scale, bm, bn)

    def fwd(x, w, a, b, scale):
        return f(x, w, a, b, scale), (x, w, a, b, scale)

    def bwd(res, g):
        x, w, a, b, scale = res
        sc = jnp.asarray(scale, x.dtype)
        g = g.astype(x.dtype)
        z = x @ a.T.astype(x.dtype)                      # (M, r) recomputed
        gz = (g @ b.astype(x.dtype)) * sc                # (M, r)
        dx = g @ w.T + gz @ a.astype(x.dtype)
        dw = (x.T @ g).astype(w.dtype)
        da = (gz.T @ x).astype(a.dtype)
        db = (g.T @ z * sc).astype(b.dtype)
        dscale = jnp.sum(g * (z @ b.T.astype(x.dtype))).astype(
            jnp.result_type(scale))
        return dx, dw, da, db, jnp.reshape(dscale, jnp.shape(scale))

    f.defvjp(fwd, bwd)
    return f


def lora_matmul(x, w, a, b, scale, bm: int = 128, bn: int = 128):
    """x: (..., din) -> (..., dout), fused base + adapter matmul
    (differentiable: reference-math backward)."""
    lead = x.shape[:-1]
    din = x.shape[-1]
    y = _lora_matmul_vjp(bm, bn)(x.reshape(-1, din), w, a, b, scale)
    return y.reshape(*lead, w.shape[1])


def _flash_attention_fwd(q, k, v, causal, window, bq, bk):
    S, T = q.shape[1], k.shape[1]
    bq = min(bq, S)
    bk = min(bk, T)
    qp, S0 = _pad_to(q, 1, bq)
    kp, T0 = _pad_to(k, 1, bk)
    vp, _ = _pad_to(v, 1, bk)
    kv_len = T0 if kp.shape[1] != T0 else 0
    out = flash_attention_kernel(qp, kp, vp, causal=causal, window=window,
                                 bq=bq, bk=bk, kv_len=kv_len,
                                 interpret=_interpret())
    return out[:, :S0]


@functools.lru_cache(maxsize=None)
def _flash_attention_vjp(causal: bool, window: int, bq: int, bk: int):
    """custom_vjp: Pallas forward, oracle-math backward (Pallas kernels
    carry no autodiff rule — without this ``use_kernels=True`` training
    cannot differentiate through attention).  The backward differentiates
    ``flash_jax`` — the same masking semantics as the kernel (causal and
    window applied independently) with O(bq·bk) live score tiles, so the
    flash memory win holds in the backward pass too; non-block-multiple
    shapes fall back to single-chunk (dense-equivalent) tiles."""

    @jax.custom_vjp
    def f(q, k, v):
        return _flash_attention_fwd(q, k, v, causal, window, bq, bk)

    def fwd(q, k, v):
        return f(q, k, v), (q, k, v)

    def bwd(res, g):
        from repro.models.attention_core import flash_jax
        q, k, v = res
        S, T = q.shape[1], k.shape[1]
        qc = 512 if S % 512 == 0 else S
        kc = 1024 if T % 1024 == 0 else T
        _, pull = jax.vjp(
            lambda q_, k_, v_: flash_jax(
                q_, k_, v_, causal=causal, window=window, q_chunk=qc,
                kv_chunk=kc).astype(q.dtype), q, k, v)
        return pull(g)

    f.defvjp(fwd, bwd)
    return f


def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128):
    """GQA flash attention.  S/T are padded to block multiples (padded KV
    columns are masked in-kernel via ``kv_len``, padded query rows are
    sliced off), so the kernel path runs at ANY sequence length — no silent
    reference fallback.  Differentiable (memory-bounded flash backward)."""
    return _flash_attention_vjp(causal, window, bq, bk)(q, k, v)


def ring_decode(q, k, v, pos, length, n_tokens=None, window: int = 0,
                k_scale=None, v_scale=None, bk: int = 128):
    """Flash-decoding over a GQA ring cache (Pallas).

    q: (B,C,H,hd); k/v: (B,cap,K,hd) raw cache storage (int8 with
    per-token (B,cap,K,1) scales fused in-kernel); pos/length/n_tokens:
    (B,) ring state AFTER the chunk write.  The slot axis is padded to a
    block multiple here (dtype-preserving — an int8 cache is never expanded
    to full precision); padded slots are masked in-kernel.  (B,C,H,hd) fp32.
    """
    B, C = q.shape[:2]
    cap = k.shape[1]
    if n_tokens is None:
        n_tokens = jnp.full((B,), C, jnp.int32)
    bk = min(bk, cap)
    k, _ = _pad_to(k, 1, bk)
    v, _ = _pad_to(v, 1, bk)
    if k_scale is not None:
        k_scale, _ = _pad_to(k_scale, 1, bk)
        v_scale, _ = _pad_to(v_scale, 1, bk)
    return ring_decode_kernel(q, k, v, pos, length, n_tokens, cap=cap,
                              k_scale=k_scale, v_scale=v_scale,
                              window=window, bk=bk, interpret=_interpret())


def mla_ring_decode(q_eff, c_kv, k_rope, pos, length, n_tokens=None, *,
                    scale: float, window: int = 0,
                    c_kv_scale=None, k_rope_scale=None, bk: int = 128):
    """Flash-decoding over the MLA compressed-latent ring cache (Pallas).

    q_eff: (B,C,H,kvr+rope) absorbed queries; c_kv/k_rope: (B,cap,·) raw
    cache storage (int8 with per-half (B,cap,1) scales fused in-kernel);
    ``scale`` is REQUIRED and must be the un-absorbed 1/√(nope+rope) — it
    is not derivable from q_eff's width.  Returns out_lat (B,C,H,kvr) fp32.
    """
    B, C = q_eff.shape[:2]
    cap = c_kv.shape[1]
    if n_tokens is None:
        n_tokens = jnp.full((B,), C, jnp.int32)
    bk = min(bk, cap)
    c_kv, _ = _pad_to(c_kv, 1, bk)
    k_rope, _ = _pad_to(k_rope, 1, bk)
    if c_kv_scale is not None:
        c_kv_scale, _ = _pad_to(c_kv_scale, 1, bk)
        k_rope_scale, _ = _pad_to(k_rope_scale, 1, bk)
    return mla_ring_decode_kernel(q_eff, c_kv, k_rope, pos, length, n_tokens,
                                  cap=cap, scale=scale,
                                  c_kv_scale=c_kv_scale,
                                  k_rope_scale=k_rope_scale,
                                  window=window, bk=bk,
                                  interpret=_interpret())


def bgmv(x, a_pages, b_pages, table, rank, scale, ids):
    """Batched-gather multi-tenant LoRA delta (Pallas): per-row
    y_b = scale_b · B_b(A_b x_b) gathered from the paged adapter pools at
    each row's own rank.

    x: (B, C, din); a_pages: (P, pr, din); b_pages: (P, dout, pr);
    table: (maxA, Pmax) adapter→pages indirection; rank/scale: (maxA,);
    ids: (B,) per-row adapter ids (0 = base, exact-zero delta).
    Returns (B, C, dout) f32.  Inference-only — no autodiff rule.
    """
    ids = ids.astype(jnp.int32)
    return bgmv_kernel(x, a_pages, b_pages, table[ids], rank[ids],
                       scale.astype(jnp.float32)[ids],
                       interpret=_interpret())


def wkv6(r, k, v, w, u, chunk: int = 256):
    """RWKV6 recurrence at any sequence length.  S is padded to the chunk
    (itself a multiple of the kernel's 16-row tile) with k = v = w = 0:
    the state passes through padded steps unchanged, and their outputs are
    sliced off."""
    S = r.shape[1]
    chunk = min(chunk, -(-S // 16) * 16)
    pads = [_pad_to(t, 1, chunk)[0] for t in (r, k, v, w)]
    return wkv6_kernel(*pads, u, chunk=chunk, interpret=_interpret())[:, :S]


def adapter_gram(x, bm: int = 512):
    """xᵀx (r, r) fp32 for any (m, r) — tail masking inside the kernel."""
    return adapter_gram_kernel(x, bm=min(bm, x.shape[0]),
                               interpret=_interpret())


# -- abstract contracts (checked by repro.analysis.contracts) -----------------
#
# Every Pallas kernel must be aval-identical to its XLA twin / oracle —
# ``pallas_call`` abstract-evals on any backend, so these hold on CPU CI.

from repro.analysis.registry import ContractCase, check_contract  # noqa: E402


@check_contract("kernel.ring_decode", families=("gqa",), mesh_sizes=(1,))
def _contract_ring_decode(case):
    from repro.analysis import fixtures as FX
    from repro.models.attention_core import ring_flash_decode
    B, C, H, K, hd, cap = 2, 4, 8, 4, 16, 64
    args = (FX.sds((B, C, H, hd), "float32"),
            FX.sds((B, cap, K, hd), "float32"),
            FX.sds((B, cap, K, hd), "float32"),
            FX.sds((B,), "int32"), FX.sds((B,), "int32"))

    def out_check(out, _case):
        assert out.shape == (B, C, H, hd) and out.dtype == jnp.float32

    return ContractCase(ring_decode, args, out_check=out_check,
                        twin=(ring_flash_decode, args))


@check_contract("kernel.mla_ring_decode", families=("mla",), mesh_sizes=(1,))
def _contract_mla_ring_decode(case):
    from repro.analysis import fixtures as FX
    from repro.models.attention_core import mla_ring_flash_decode
    B, C, H, kvr, rope, cap = 2, 4, 4, 32, 16, 64
    scale = (kvr + rope) ** -0.5
    args = (FX.sds((B, C, H, kvr + rope), "float32"),
            FX.sds((B, cap, kvr), "float32"),
            FX.sds((B, cap, rope), "float32"),
            FX.sds((B,), "int32"), FX.sds((B,), "int32"))

    def out_check(out, _case):
        assert out.shape == (B, C, H, kvr) and out.dtype == jnp.float32

    return ContractCase(functools.partial(mla_ring_decode, scale=scale), args,
                        out_check=out_check,
                        twin=(functools.partial(mla_ring_flash_decode, scale=scale),
                              args))


@check_contract("kernel.flash_attention", families=("gqa",), mesh_sizes=(1,))
def _contract_flash_attention(case):
    from repro.analysis import fixtures as FX
    from repro.kernels.ref import flash_attention_ref
    B, S, H, K, hd = 2, 16, 8, 4, 16
    args = (FX.sds((B, S, H, hd), "float32"),
            FX.sds((B, S, K, hd), "float32"),
            FX.sds((B, S, K, hd), "float32"))
    return ContractCase(flash_attention, args,
                        twin=(flash_attention_ref, args))


@check_contract("kernel.lora_matmul", families=("gqa",), mesh_sizes=(1,))
def _contract_lora_matmul(case):
    from repro.analysis import fixtures as FX
    from repro.kernels.ref import lora_matmul_ref
    B, S, din, dout, r = 2, 8, 32, 24, 4
    args = (FX.sds((B, S, din), "float32"),
            FX.sds((din, dout), "float32"),
            FX.sds((r, din), "float32"),
            FX.sds((dout, r), "float32"), 2.0)
    return ContractCase(lora_matmul, args, twin=(lora_matmul_ref, args))


@check_contract("kernel.wkv6", families=("ssm",), mesh_sizes=(1,))
def _contract_wkv6(case):
    from repro.analysis import fixtures as FX
    from repro.kernels.ref import wkv6_ref
    B, S, H, hd = 2, 8, 4, 16
    args = tuple(FX.sds((B, S, H, hd), "float32") for _ in range(4)) \
        + (FX.sds((H, hd), "float32"),)
    return ContractCase(wkv6, args, twin=(wkv6_ref, args))


@check_contract("kernel.adapter_gram", families=("gqa",), mesh_sizes=(1,))
def _contract_adapter_gram(case):
    from repro.analysis import fixtures as FX
    from repro.kernels.ref import adapter_gram_ref
    args = (FX.sds((100, 12), "float32"),)
    return ContractCase(adapter_gram, args, twin=(adapter_gram_ref, args))


@check_contract("kernel.bgmv", families=("gqa",), mesh_sizes=(1,))
def _contract_bgmv(case):
    """The paged multi-tenant LoRA delta: the Pallas bgmv path and the XLA
    gather/einsum twin must agree on avals through ``paged_lora_delta``."""
    from repro.analysis import fixtures as FX
    from repro.peft.lora import PagedLoRA, paged_lora_delta
    B, C, din, dout = 4, 4, 32, 24
    P, pr, maxA, Pmax = 8, 4, 4, 2
    leaves = (FX.sds((P, pr, din), "float32"),      # a_pages
              FX.sds((P, dout, pr), "float32"),     # b_pages
              FX.sds((maxA,), "float32"),           # scale
              FX.sds((maxA, Pmax), "int32"),        # table
              FX.sds((maxA,), "int32"),             # rank
              FX.sds((B,), "int32"))                # ids
    x = FX.sds((B, C, din), "float32")

    def run(impl):
        def f(x, a, b, s, t, r, i):
            return paged_lora_delta(x, PagedLoRA(a, b, s, t, r, i, impl=impl))
        return f

    args = (x,) + leaves
    return ContractCase(run("kernel"), args, twin=(run("xla"), args))
