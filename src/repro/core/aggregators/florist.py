"""FLoRIST (Algorithm 1, server block): stacked thin-SVDs + r×r core SVD +
per-layer energy thresholding — the singular values of ΔW without ever
forming ΔW."""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax.numpy as jnp
import numpy as np

import jax

from repro.common import telemetry
from repro.core.aggregators.base import (AggResult, Aggregator,
                                         adapter_leaf_paths, bucket_by_shape,
                                         fold_scale, get_path,
                                         register_aggregator, set_path)
from repro.core.svd import (florist_core_batched, florist_core_delta_batched,
                            florist_core_stacked)


@register_aggregator("florist")
class FloristAggregator(Aggregator):
    """Streaming stacker + thresholded core SVD at finalize.

    ``add_client`` folds each arriving client into a *bounded* compact
    intermediate: scale-folded B blocks and weighted A blocks are appended
    to a per-leaf pending list and, every ``flush_every`` arrivals, the
    pending blocks are compacted on device —

    * **stacked mode** (small rounds): the pending blocks are concatenated,
      widest first, into one (L, m, Σr) / (L, Σr, n) pair, the exact
      intermediate the paper's pipeline thin-SVDs at finalize;
    * **delta mode** (``stream="delta"``, or ``"auto"`` once the stack
      width Σ r_k would exceed ``min(m, n)``): the pending blocks are
      contracted into a running dense update ``M += B_pend A_pend`` —
      O(m·n) per leaf, *constant in the client count* — and finalize runs
      the thin SVD of ``M`` directly (the same SVD the stacked route
      computes implicitly, so the two modes agree up to fp error).

    Either way the server never holds more than ``flush_every`` client
    blocks plus one compact intermediate per leaf: peak live adapter
    memory is O(cohort), not O(K).  ``peak_pending_blocks`` records the
    high-water mark for the memory-bound tests.

    ``finalize`` buckets leaves with identical intermediate shapes so every
    layer of a bucket goes through ONE compiled vmapped call
    (:func:`~repro.core.svd.florist_core_batched` /
    :func:`~repro.core.svd.florist_core_delta_batched`); spectra and
    concrete per-layer ranks are materialized with a single device→host
    transfer at the end, where the zero-padded outputs are truncated.
    Ragged per-layer ranks are zero-padded to the per-leaf max so the
    global tree stays scan-compatible; the true ranks are recorded for
    communication accounting.

    ``pipeline="loop"`` keeps the legacy per-(leaf, layer) Python loop
    (one eager ``florist_core_stacked`` + host sync per layer) as a
    reference for equivalence tests and the ``agg_bench`` baseline; it
    forces stacked mode (the loop oracle predates the delta route).
    """

    def __init__(self, tau=0.9, svd_method: str = "svd", max_rank: int = 0,
                 pipeline: str = "batched", stream: str = "auto",
                 flush_every: int = 64):
        if pipeline not in ("batched", "loop"):
            raise ValueError(pipeline)
        if stream not in ("auto", "stacked", "delta"):
            raise ValueError(stream)
        self.tau = tau
        self.svd_method = svd_method
        self.max_rank = max_rank
        self.pipeline = pipeline
        # the loop oracle iterates the stacked lists directly
        self.stream = "stacked" if pipeline == "loop" else stream
        self.flush_every = max(1, int(flush_every))
        self.peak_pending_blocks = 0
        super().__init__()

    # -- streaming accumulation ----------------------------------------------

    def _accumulate(self, update: Dict, weight: float, rank: int) -> None:
        for path in adapter_leaf_paths(update):
            Bk, Ak = fold_scale(get_path(update, path))
            acc = self._state.setdefault(
                path, {"stacked": Ak.ndim == 3, "A": [], "B": [], "M": None,
                       "widths": ()})
            acc["B"].append(Bk)
            acc["A"].append(weight * Ak)
            self.peak_pending_blocks = max(self.peak_pending_blocks,
                                           len(acc["B"]))
            if len(acc["B"]) >= self.flush_every:
                self._compact(acc)

    def _delta_mode(self, acc: Dict) -> bool:
        if acc["M"] is not None or self.stream == "delta":
            return True
        if self.stream != "auto" or not acc["B"]:
            return False
        width = sum(b.shape[-1] for b in acc["B"])
        m, n = acc["B"][0].shape[-2], acc["A"][0].shape[-1]
        return width > min(m, n)

    def _compact(self, acc: Dict) -> None:
        """Fold the pending client blocks into the compact intermediate
        (running dense ΔW in delta mode, one consolidated stack otherwise),
        bounding the pending list at ``flush_every`` entries."""
        if not acc["B"]:
            return
        # widest block first, arrival order among equal widths (a stable
        # sort), one permutation for the B columns and the A rows: ΔW =
        # Σ B_k A_k is unchanged, and the concatenation's shape signature
        # follows from the rank multiset alone, so the eager concatenate
        # compiles once per multiset rather than once per arrival order
        order = sorted(range(len(acc["B"])),
                       key=lambda i: -acc["B"][i].shape[-1])
        Bs = [acc["B"][i] for i in order]
        As = [acc["A"][i] for i in order]
        if len(Bs) > 1 or not acc["widths"]:
            acc["widths"] = tuple(b.shape[-1] for b in Bs)
        B = Bs[0] if len(Bs) == 1 else jnp.concatenate(Bs, axis=-1)
        A = As[0] if len(As) == 1 else jnp.concatenate(As, axis=-2)
        if self._delta_mode(acc):
            d = B @ A                       # (L, m, n) / (m, n): batched matmul
            acc["M"] = d if acc["M"] is None else acc["M"] + d
            acc["B"], acc["A"] = [], []
        else:
            acc["B"], acc["A"] = [B], [A]

    def _settle(self) -> Dict[Tuple, Tuple]:
        """Compact every leaf and return its finalize-ready intermediate:
        ``("stack", B (L,m,Σr), A (L,Σr,n))`` or ``("delta", M (L,m,n))``
        (un-stacked leaves get a singleton layer axis so every leaf is
        3-D)."""
        inter: Dict[Tuple, Tuple] = {}
        for path, acc in self._state.items():
            self._compact(acc)
            if acc["M"] is not None:
                M = acc["M"] if acc["stacked"] else acc["M"][None]
                inter[path] = ("delta", M)
            else:
                B, A = acc["B"][0], acc["A"][0]
                if not acc["stacked"]:
                    B, A = B[None], A[None]
                inter[path] = ("stack", B, A)
        return inter

    def _leaf_stacks(self) -> Dict[Tuple, Tuple[jnp.ndarray, jnp.ndarray]]:
        """{path: (B_stack (L,m,Σr), A_stack (L,Σr,n))} — stacked-mode
        leaves only (the loop oracle and stacked-only callers)."""
        stacks = {}
        for path, acc in self._state.items():
            B_stack = jnp.concatenate(acc["B"], axis=-1)
            A_stack = jnp.concatenate(acc["A"], axis=-2)
            if not acc["stacked"]:
                B_stack, A_stack = B_stack[None], A_stack[None]
            stacks[path] = (B_stack, A_stack)
        return stacks

    # -- finalize -------------------------------------------------------------

    def _materialize(self, device: Dict[Tuple, Tuple]) -> AggResult:
        """Shared finalize tail: ONE device→host transfer for all leaves'
        spectra + ranks, then truncate the zero-padded global factors to
        each leaf's max kept rank (exact: the dropped columns are zeros)."""
        out: Dict = {}
        rank_rec: Dict[Tuple, List[int]] = {}
        spectra: Dict[Tuple, List[np.ndarray]] = {}
        with telemetry.span("finalize.wait"):
            host = jax.device_get({p: (v[2], v[3])
                                   for p, v in device.items()})
        with telemetry.span("finalize.build"):
            for path, (Bg, Ag, _, _) in device.items():
                sp_h, p_h = host[path]
                ps = [int(x) for x in p_h]
                p_max = max(ps)
                Bg, Ag = Bg[:, :, :p_max], Ag[:, :p_max, :]
                if not self._state[path]["stacked"]:
                    Bg, Ag = Bg[0], Ag[0]
                set_path(out, path, {"A": Ag, "B": Bg,
                                     "scale": self._ref_scales[path]})
                rank_rec[path] = ps
                spectra[path] = [np.asarray(s) for s in sp_h]
        return AggResult(self.name, out, None, rank_rec, spectra)

    def _finalize(self) -> AggResult:
        if self.pipeline == "loop":
            return self._finalize_loop()
        with telemetry.span("finalize.core") as s:
            device = self._dispatch_cores()
            s.set(stack_widths=next(iter(self._state.values()))["widths"])
        return self._materialize(device)

    def _dispatch_cores(self) -> Dict[Tuple, Tuple]:
        """Settle the accumulators and dispatch the batched cores; returns
        {path: (B_g, A_g, spectra, ranks)} still on the device."""
        inter = self._settle()
        stacks = {p: v[1:] for p, v in inter.items() if v[0] == "stack"}
        deltas = {p: v[1:] for p, v in inter.items() if v[0] == "delta"}
        # bucket leaves by intermediate shape: equal-shaped leaves (e.g. all
        # the q/k/v/o projections) share one compiled call over G·L layers
        device: Dict[Tuple, Tuple] = {}
        for paths in bucket_by_shape(stacks):
            Bb = jnp.concatenate([stacks[p][0] for p in paths], axis=0)
            Ab = jnp.concatenate([stacks[p][1] for p in paths], axis=0)
            Bg, Ag, sp, pr = florist_core_batched(
                Bb, Ab, self.tau, self.svd_method, self.max_rank)
            L = stacks[paths[0]][0].shape[0]
            for i, path in enumerate(paths):
                sl = slice(i * L, (i + 1) * L)
                device[path] = (Bg[sl], Ag[sl], sp[sl], pr[sl])
        for paths in bucket_by_shape(deltas):
            Mb = jnp.concatenate([deltas[p][0] for p in paths], axis=0)
            Bg, Ag, sp, pr = florist_core_delta_batched(
                Mb, self.tau, self.svd_method, self.max_rank)
            L = deltas[paths[0]][0].shape[0]
            for i, path in enumerate(paths):
                sl = slice(i * L, (i + 1) * L)
                device[path] = (Bg[sl], Ag[sl], sp[sl], pr[sl])
        return device

    def _finalize_loop(self) -> AggResult:
        """Legacy per-(leaf, layer) eager loop — kept verbatim as the
        equivalence oracle and benchmark baseline."""
        out: Dict = {}
        rank_rec: Dict[Tuple, List[int]] = {}
        spectra: Dict[Tuple, List[np.ndarray]] = {}
        for path, acc in self._state.items():
            stacked = acc["stacked"]
            B_stack = jnp.concatenate(acc["B"], axis=-1)   # (L, m, Σr)
            A_stack = jnp.concatenate(acc["A"], axis=-2)   # (L, Σr, n)
            L = B_stack.shape[0] if stacked else 1
            Bg_l, Ag_l, ps = [], [], []
            spectra[path] = []
            for l in range(L):
                res = florist_core_stacked(
                    B_stack[l] if stacked else B_stack,
                    A_stack[l] if stacked else A_stack,
                    self.tau, self.svd_method, self.max_rank)
                Bg_l.append(res.B_g)
                Ag_l.append(res.A_g)
                ps.append(res.p)
                spectra[path].append(np.asarray(res.spectrum))
            p_max = max(ps)
            if stacked:
                Bg = jnp.stack([jnp.pad(b, ((0, 0), (0, p_max - b.shape[1])))
                                for b in Bg_l])
                Ag = jnp.stack([jnp.pad(a, ((0, p_max - a.shape[0]), (0, 0)))
                                for a in Ag_l])
            else:
                Bg, Ag = Bg_l[0], Ag_l[0]
            set_path(out, path, {"A": Ag, "B": Bg,
                                 "scale": self._ref_scales[path]})
            rank_rec[path] = ps
        return AggResult(self.name, out, None, rank_rec, spectra)

    def server_flops(self, dims, client_ranks, agg_ranks=None) -> int:
        from repro.core.costs import SVD_CONST

        r = sum(client_ranks)                        # stacked rank
        total = 0
        for path, (L, n, m) in dims.items():
            for l in range(L):
                total += SVD_CONST * (m * r * r + n * r * r)  # thin SVDs
                total += 2 * r ** 3                            # Q = V_Bᵀ U_A
                total += 2 * r * r                             # P diag scaling
                total += SVD_CONST * r ** 3                    # SVD(P)
                p_l = agg_ranks[path][l] if agg_ranks else r
                total += 2 * (m * r * p_l + p_l * r * n)       # build B_g, A_g
        return total
