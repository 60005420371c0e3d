"""FLoRIST's efficient SVD pipeline (paper §3, Eqs. 1–4).

Given client adapters ``B_k ∈ R^{m×r_k}``, ``A_k ∈ R^{r_k×n}`` and weights
``w_k = n_k / N``:

    B_stack = [B_1 | ... | B_K]              (m × r),  r = Σ r_k
    A_stack = [w_1 A_1 ; ... ; w_K A_K]      (r × n)
    ΔW      = B_stack A_stack                 (never formed!)

    B_stack = U_B S_B V_Bᵀ,  A_stack = U_A S_A V_Aᵀ          (thin SVDs)
    Q = V_Bᵀ U_A,  P = S_B Q S_A ∈ R^{r×r}                    (Eq. 2)
    SVD(P) = U_P S_P V_Pᵀ  →  singular values of ΔW are S_P   (exact)
    B_g = (U_B U_P)[:, :p] S_P[:p,:p],  A_g = (V_Pᵀ V_Aᵀ)[:p, :]   (Eq. 3)

with ``p`` from the energy threshold (Eq. 6):
    p = min { p : Σ_{i≤p} σ_i² / Σ_i σ_i² ≥ τ }.

Two thin-SVD backends:
  * ``svd``  — LAPACK/XLA divide-and-conquer (default; exact),
  * ``gram`` — eigh of the r×r Gram matrix (TPU-idiomatic for tall-skinny
    stacks: two MXU matmuls + small eigh instead of an m×r Householder
    pipeline; see DESIGN.md §3).
"""
from __future__ import annotations

import functools

from typing import Dict, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp


class SVDResult(NamedTuple):
    u: jnp.ndarray
    s: jnp.ndarray
    vt: jnp.ndarray


def thin_svd(x: jnp.ndarray, method: str = "svd") -> SVDResult:
    """Thin SVD of x (m×n, any aspect). method: 'svd' | 'gram'."""
    if method == "svd":
        u, s, vt = jnp.linalg.svd(x, full_matrices=False)
        return SVDResult(u, s, vt)
    if method == "gram":
        return gram_svd(x)
    raise ValueError(method)


@functools.lru_cache(maxsize=None)
def _batched_thin_svd_fn(method: str):
    return jax.jit(jax.vmap(lambda x: tuple(thin_svd(x, method))))


def thin_svd_batched(x: jnp.ndarray, method: str = "svd") -> SVDResult:
    """Thin SVD over a stack of equal-shaped matrices x (L, m, n) in ONE
    compiled call — the building block of the batched server pipeline."""
    u, s, vt = _batched_thin_svd_fn(method)(x)
    return SVDResult(u, s, vt)


def _gram_matrix(x: jnp.ndarray) -> jnp.ndarray:
    """xᵀx in fp32.  On TPU this is the streaming Pallas ``adapter_gram``
    kernel (m-panels through VMEM, r×r accumulator resident); on CPU /
    under interpret the plain-XLA reference is both the oracle and the
    faster choice, so we fall back to it."""
    if jax.default_backend() == "tpu":
        from repro.kernels.ops import adapter_gram
        return adapter_gram(x)
    xf = x.astype(jnp.float32)
    return xf.T @ xf


def gram_svd(x: jnp.ndarray) -> SVDResult:
    """Thin SVD via the Gram trick (TPU route).

    For tall x (m ≥ n): eigh(xᵀx) = V diag(s²) Vᵀ; U = x V / s.
    For wide x: transpose, recurse, swap.  Numerically fine for LoRA-scale
    conditioning (σ_max/σ_min ≪ 1/√eps in fp32); exactness is asserted
    against the LAPACK route in tests.

    Rank-deficient stacks (e.g. duplicated clients) produce near-null
    eigenvalues whose U columns would otherwise be garbage-magnitude noise
    (x·v ≈ 0 divided by s ≈ 0): columns with σ below a scaled tolerance
    (σ_max·√(n·eps), the Gram route's resolution limit) are zeroed, which
    leaves U S Vᵀ unchanged to within the tolerance.
    """
    m, n = x.shape
    if m < n:
        r = gram_svd(x.T)
        return SVDResult(r.vt.T, r.s, r.u.T)
    g = _gram_matrix(x)                            # (n, n)
    w, v = jnp.linalg.eigh(g)                      # ascending
    w = w[::-1]
    v = v[:, ::-1]
    s = jnp.sqrt(jnp.clip(w, 0.0))
    eps = jnp.finfo(s.dtype).eps
    tol = s[0] * jnp.sqrt(eps * n)
    u = jnp.where(s[None, :] > tol,
                  (x @ v) / jnp.maximum(s, tol)[None, :], 0.0)
    return SVDResult(u, s, v.T)


def energy_rank_traced(s: jnp.ndarray, tau: float) -> jnp.ndarray:
    """Smallest p with Σ_{i≤p} σ_i² / Σ σ_i² ≥ τ, as a traced int32 scalar.

    This is the single source of truth for energy-rank semantics: fp32
    cumulative energy and an fp32 τ comparison, identical under jit and on
    host (``energy_rank`` is a thin ``int()`` wrapper), so the padded /
    batched / sharded paths pick the same p as the host loop at τ
    boundaries.
    """
    e = jnp.cumsum(s.astype(jnp.float32) ** 2)
    frac = e / jnp.maximum(e[-1], 1e-30)
    p = jnp.searchsorted(frac, jnp.float32(tau), side="left") + 1
    return jnp.minimum(p, s.shape[0]).astype(jnp.int32)


def energy_rank(s: jnp.ndarray, tau: float) -> int:
    """Host-side energy rank (concrete int) — same fp32 semantics as
    :func:`energy_rank_traced` by construction."""
    return int(energy_rank_traced(s, tau))


def knee_rank_traced(s: jnp.ndarray) -> jnp.ndarray:
    """Traced knee-point rank: max distance of the cumulative-energy curve
    from the chord between (0, 0) and (r, 1).  int32 scalar in [1, r]."""
    e = jnp.cumsum(s.astype(jnp.float32) ** 2)
    frac = e / jnp.maximum(e[-1], 1e-30)               # (r,)
    r = s.shape[0]
    x = (jnp.arange(1, r + 1, dtype=jnp.float32)) / r
    # distance from the chord y = x (both endpoints normalized)
    p = jnp.argmax(frac - x) + 1
    return jnp.clip(p, 1, r).astype(jnp.int32)


def knee_rank(s: jnp.ndarray) -> int:
    """BEYOND-PAPER (paper §5 future work (i)): automatic per-layer rank
    selection by knee-point detection on the cumulative-energy curve.
    No tunable τ; adapts to each layer's spectrum shape.  Host wrapper of
    :func:`knee_rank_traced` (same semantics traced and concrete)."""
    return int(knee_rank_traced(s))


def stack_adapters(Bs: Sequence[jnp.ndarray], As: Sequence[jnp.ndarray],
                   weights: Sequence[float]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Weighted stacking (paper: weights fold into A_stack)."""
    B_stack = jnp.concatenate(list(Bs), axis=1)                      # (m, r)
    A_stack = jnp.concatenate([w * A for w, A in zip(weights, As)], axis=0)
    return B_stack, A_stack


class FloristOut(NamedTuple):
    B_g: jnp.ndarray          # (m, p)  — includes S_P scaling
    A_g: jnp.ndarray          # (p, n)
    spectrum: jnp.ndarray     # full S_P (r,)
    p: int


def florist_core_stacked(B_stack: jnp.ndarray, A_stack: jnp.ndarray, tau,
                         svd_method: str = "svd",
                         max_rank: int = 0) -> FloristOut:
    """FLoRIST server pipeline on pre-stacked blocks (B_stack (m, r),
    A_stack (r, n) with weights already folded into A_stack) — the entry
    point for the streaming aggregator, which accumulates the stacks
    incrementally as clients arrive."""
    f32 = jnp.float32
    B_stack, A_stack = B_stack.astype(f32), A_stack.astype(f32)
    ub, sb, vbt = thin_svd(B_stack, svd_method)
    ua, sa, vat = thin_svd(A_stack, svd_method)
    q = vbt @ ua                                   # (r, r)
    p_core = (sb[:, None] * q) * sa[None, :]       # P = S_B Q S_A
    up, sp, vpt = thin_svd(p_core, "svd")          # r×r — always LAPACK-size
    p = knee_rank(sp) if tau == "auto" else energy_rank(sp, tau)
    if max_rank:
        p = min(p, max_rank)
    B_g = (ub @ up)[:, :p] * sp[None, :p]
    A_g = (vpt @ vat)[:p, :]
    return FloristOut(B_g, A_g, sp, p)


def florist_core(Bs: Sequence[jnp.ndarray], As: Sequence[jnp.ndarray],
                 weights: Sequence[float], tau,
                 svd_method: str = "svd", max_rank: int = 0) -> FloristOut:
    """The full FLoRIST server pipeline for one weight matrix (Alg. 1,
    server block).  Host-side: returns concretely-truncated adapters.
    tau: float in (0,1], or "auto" for knee-point rank selection
    (beyond-paper; paper §5 future-work (i))."""
    B_stack, A_stack = stack_adapters(Bs, As, weights)
    return florist_core_stacked(B_stack, A_stack, tau, svd_method, max_rank)


def florist_core_padded(B_stack: jnp.ndarray, A_stack: jnp.ndarray, tau,
                        svd_method: str = "svd", max_rank: int = 0):
    """Jit-safe variant: full-rank outputs with columns ≥ p zeroed (same ΔW).

    Used by the sharded multi-pod aggregation and the batched (vmapped)
    server pipeline, where shapes must be static.  Honors the same knobs as
    the host path: ``tau`` is a float threshold or ``"auto"`` (knee-point),
    and ``max_rank`` caps the kept rank — so sharded/batched backends
    produce the same ΔW as host ``florist`` under any configuration.
    Returns (B_g_full (m,r), A_g_full (r,n), spectrum (r,), p int32).
    """
    f32 = jnp.float32
    B_stack, A_stack = B_stack.astype(f32), A_stack.astype(f32)
    ub, sb, vbt = thin_svd(B_stack, svd_method)
    ua, sa, vat = thin_svd(A_stack, svd_method)
    q = vbt @ ua
    p_core = (sb[:, None] * q) * sa[None, :]
    up, sp, vpt = thin_svd(p_core, "svd")
    p = knee_rank_traced(sp) if tau == "auto" else energy_rank_traced(sp, tau)
    if max_rank:
        p = jnp.minimum(p, max_rank)
    r = sp.shape[0]
    keep = (jnp.arange(r) < p)
    B_g = (ub @ up) * jnp.where(keep, sp, 0.0)[None, :]
    A_g = (vpt @ vat) * keep[:, None]
    return B_g, A_g, sp, p


@functools.lru_cache(maxsize=None)
def _batched_core_fn(tau, svd_method: str, max_rank: int):
    core = functools.partial(florist_core_padded, tau=tau,
                             svd_method=svd_method, max_rank=max_rank)

    def florist_core(B_stacks, A_stacks):
        return jax.vmap(core)(B_stacks, A_stacks)

    return jax.jit(florist_core)         # executable ``jit_florist_core``


def florist_core_batched(B_stacks: jnp.ndarray, A_stacks: jnp.ndarray, tau,
                         svd_method: str = "svd", max_rank: int = 0):
    """Batched FLoRIST server pipeline: ONE compiled call for a whole stack
    of layers (or a bucket of equal-shaped leaves × layers).

    ``jax.vmap`` of :func:`florist_core_padded` over axis 0, jitted and
    cached per (τ, backend, cap) — all thin SVDs for all layers run in a
    single XLA computation with no per-layer retrace or host sync.  The
    caller materializes spectra/ranks with one device→host transfer at the
    end and truncates the zero-padded outputs there.

    B_stacks: (L, m, r), A_stacks: (L, r, n), weights already folded in.
    Returns (B_g (L,m,r) zero-padded beyond each layer's p_l, A_g (L,r,n),
    spectra (L,r), ranks (L,) int32).
    """
    return _batched_core_fn(tau, svd_method, int(max_rank))(B_stacks, A_stacks)


def florist_core_delta_padded(M: jnp.ndarray, tau, svd_method: str = "svd",
                              max_rank: int = 0):
    """Jit-safe FLoRIST core on an *accumulated* update ΔW = Σ_k w_k B_k A_k.

    The stacked pipeline (:func:`florist_core_padded`) computes the SVD of
    ``B_stack A_stack`` — exactly the SVD of ΔW — without forming ΔW, which
    is the compact route while the stack width Σ r_k stays below
    ``min(m, n)``.  Past that point (hundreds of clients per round) the
    dense ΔW itself is the *smaller* intermediate, so the streaming
    aggregator contracts arriving blocks into a running ``M`` and this core
    finishes the job: one thin SVD of ``M`` and the same energy threshold /
    knee selection / rank cap as the stacked path (identical ΔW up to fp).

    Returns (B_g (m, q), A_g (q, n), spectrum (q,), p int32) with
    q = min(m, n) and columns ≥ p zeroed, mirroring the padded stacked core.
    """
    M = M.astype(jnp.float32)
    u, s, vt = thin_svd(M, svd_method)
    p = knee_rank_traced(s) if tau == "auto" else energy_rank_traced(s, tau)
    if max_rank:
        p = jnp.minimum(p, max_rank)
    keep = (jnp.arange(s.shape[0]) < p)
    B_g = u * jnp.where(keep, s, 0.0)[None, :]
    A_g = vt * keep[:, None]
    return B_g, A_g, s, p


@functools.lru_cache(maxsize=None)
def _batched_delta_fn(tau, svd_method: str, max_rank: int):
    core = functools.partial(florist_core_delta_padded, tau=tau,
                             svd_method=svd_method, max_rank=max_rank)

    def florist_core_delta(Ms):
        return jax.vmap(core)(Ms)

    return jax.jit(florist_core_delta)   # ``jit_florist_core_delta``


def florist_core_delta_batched(Ms: jnp.ndarray, tau,
                               svd_method: str = "svd", max_rank: int = 0):
    """Batched delta core: ONE compiled call for a layer stack of
    accumulated updates.  Ms: (L, m, n).  Returns (B_g (L, m, q),
    A_g (L, q, n), spectra (L, q), ranks (L,) int32), q = min(m, n)."""
    return _batched_delta_fn(tau, svd_method, int(max_rank))(Ms)


def reconstruction_error(Bs, As, weights, B_g, A_g) -> float:
    """‖ΔW − B_g A_g‖_F computed without forming ΔW twice (small shapes in
    tests — forms it once)."""
    dw = sum(w * (B @ A) for w, B, A in zip(weights, Bs, As))
    return float(jnp.linalg.norm(dw - B_g @ A_g))


def eckart_young_bound(spectrum: jnp.ndarray, p: int) -> float:
    """(Σ_{i>p} σ_i²)^{1/2} — the paper's Eq. 5 bound."""
    tail = spectrum[p:]
    return float(jnp.sqrt(jnp.sum(tail.astype(jnp.float32) ** 2)))
