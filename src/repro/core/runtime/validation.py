"""Server-side update validation / quarantine gate.

PR 9's streaming aggregation made the server fold irreversible: once
``Aggregator.add_client`` has folded an update into the delta-mode
accumulator there is no way to subtract it back out, so one NaN, one
corrupted leaf, or one adversarially scaled client poisons the global
adapters for everyone.  The :class:`ValidationGate` sits in front of
every fold and screens each arriving update against three contracts:

* **finiteness** — every wire tensor (A, B) and the scale header must be
  free of NaN/Inf (a single NaN in the FLoRIST accumulator propagates to
  every singular value at finalize);
* **structure** — leaf paths, layer counts and (n_in, m_out) dims must
  match the round's reference dims, and the update's A/B rank dims must
  agree with each other and with the client's assigned task rank;
* **at-most-once** — duplicate deliveries of the same task (an
  at-least-once wire re-send) fold only once.

Norm-outlier quarantine needs to see the whole round before judging any
one client, which conflicts with streaming; the gate therefore has three
modes trading robustness against server memory:

``off``
    bypass — every submit folds immediately, exactly the pre-gate path.
``screen`` (default)
    streaming: finiteness/structure/duplicate checks per update, then an
    immediate fold.  O(1) extra memory, numerically identical to ``off``
    when nothing is rejected (same folds, same order, same weights).
``full``
    buffered: updates are held until :meth:`finish`, which computes a
    robust z-score on each update's delta L2 norm (median/MAD across the
    round, with a relative floor so a tight honest cluster — e.g. every
    client clipped to the same DP bound C — never self-rejects),
    quarantines outliers, renormalizes the surviving weights to the
    round's total mass (only when something was rejected, preserving
    bit-exactness for clean rounds), and folds survivors in arrival
    order.  Costs O(participants) held updates — the PR 9 streaming
    memory bound is deliberately given up for robustness.

Either way :meth:`finish` enforces the round quorum: fewer than
``min_clients`` accepted updates marks the round failed
(``quorum_met=False``) and the trainer keeps the previous global state
instead of finalizing a half-empty accumulator.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional

import numpy as np

from repro.common import telemetry
from repro.core.aggregators.base import (adapter_leaf_paths, get_path,
                                         leaf_dims)

#: robust-σ consistency constant: σ ≈ 1.4826 · MAD for a normal sample
_MAD_SIGMA = 1.4826
#: MAD floor, relative to the median norm — an honest cluster tighter
#: than this (e.g. all updates clipped to the same DP bound) never
#: self-rejects on numerically-tiny spread
_REL_FLOOR = 0.05


@dataclasses.dataclass
class GateStats:
    """One round's validation outcome (returned by
    :meth:`ValidationGate.finish`)."""
    submitted: int = 0
    accepted: int = 0
    rejected_nonfinite: int = 0
    rejected_shape: int = 0
    rejected_duplicate: int = 0
    quarantined: int = 0
    quorum_met: bool = True

    @property
    def rejected(self) -> int:
        return (self.rejected_nonfinite + self.rejected_shape
                + self.rejected_duplicate)


@dataclasses.dataclass
class _Held:
    """One buffered submission awaiting the full-mode round verdict."""
    update: Dict
    weight: float
    rank: Optional[int]
    norm: float


class ValidationGate:
    """Validates client updates before they reach ``add_client``.

    Lifecycle mirrors the aggregator: ``begin_round(aggregator)`` →
    ``submit(...)`` per arriving update → ``finish()`` → read the
    returned :class:`GateStats` (including the quorum verdict).
    """

    def __init__(self, mode: str = "screen", mad_threshold: float = 6.0,
                 min_clients: int = 1, min_mad_samples: int = 4):
        if mode not in ("off", "screen", "full"):
            raise ValueError(f"unknown validation mode {mode!r} "
                             f"(valid: off, screen, full)")
        self.mode = mode
        self.mad_threshold = float(mad_threshold)
        self.min_clients = int(min_clients)
        self.min_mad_samples = int(min_mad_samples)
        self._agg = None
        self._dims: Optional[Dict] = None
        # id(task) -> task; holding the task pins its id for the round, so
        # a garbage-collected delivery can never alias a later one
        self._seen: Dict[int, Any] = {}
        self._held: List[_Held] = []
        self.stats = GateStats()

    # -- lifecycle ------------------------------------------------------------

    def begin_round(self, aggregator, dims: Optional[Dict] = None) -> None:
        self._agg = aggregator
        self._dims = dims
        self._seen = {}
        self._held = []
        self.stats = GateStats()

    def submit(self, task: Any, update: Dict, weight: float,
               rank: Optional[int] = None,
               init_adapters: Optional[Dict] = None) -> bool:
        """Screen one arriving update; fold it (``screen``/``off``) or
        hold it for the round verdict (``full``).  Returns False iff the
        update was rejected outright."""
        client = getattr(task, "client_id", None)
        with telemetry.span("gate", **({} if client is None
                                       else {"client": client})):
            return self._submit(task, update, weight, rank, init_adapters)

    def _submit(self, task, update, weight, rank, init_adapters) -> bool:
        self.stats.submitted += 1
        if self.mode == "off":
            self._agg.add_client(update, weight, rank=rank)
            self.stats.accepted += 1
            return True
        if task is not None:
            key = id(task)
            if key in self._seen:
                self.stats.rejected_duplicate += 1
                return False
            self._seen[key] = task
        if not self._check_structure(update, rank):
            self.stats.rejected_shape += 1
            return False
        if not self._check_finite(update):
            self.stats.rejected_nonfinite += 1
            return False
        if self.mode == "screen":
            self._agg.add_client(update, weight, rank=rank)
            self.stats.accepted += 1
            return True
        self._held.append(_Held(update, float(weight), rank,
                                _delta_norm(update, init_adapters)))
        return True

    def finish(self) -> GateStats:
        """Close the round: full-mode quarantine + fold, then the quorum
        verdict.  Idempotent per ``begin_round``."""
        if self.mode == "full" and self._held:
            self._fold_held()
        self.stats.quorum_met = self.stats.accepted >= self.min_clients
        return self.stats

    # -- checks ---------------------------------------------------------------

    def _check_structure(self, update: Dict, rank: Optional[int]) -> bool:
        try:
            dims = leaf_dims(update)
        except (KeyError, AttributeError, IndexError):
            return False
        if self._dims is None:
            self._dims = dims
        elif dims != self._dims:
            return False
        for path in adapter_leaf_paths(update):
            leaf = get_path(update, path)
            r_a, r_b = leaf["A"].shape[-2], leaf["B"].shape[-1]
            if r_a != r_b or (rank is not None and r_a != rank):
                return False
        return True

    def _check_finite(self, update: Dict) -> bool:
        for path in adapter_leaf_paths(update):
            leaf = get_path(update, path)
            for name in ("A", "B", "scale"):
                if name in leaf and not bool(
                        np.all(np.isfinite(np.asarray(leaf[name])))):
                    return False
        return True

    # -- full-mode round verdict ----------------------------------------------

    def _fold_held(self) -> None:
        held = self._held
        reject: set = set()
        if len(held) >= self.min_mad_samples:
            norms = np.array([h.norm for h in held], np.float64)
            med = float(np.median(norms))
            mad = float(np.median(np.abs(norms - med)))
            denom = max(_MAD_SIGMA * mad, _REL_FLOOR * abs(med), 1e-12)
            for i, n in enumerate(norms):
                if abs(float(n) - med) / denom > self.mad_threshold:
                    reject.add(i)
        accepted = [h for i, h in enumerate(held) if i not in reject]
        self.stats.quarantined = len(reject)
        factor = 1.0
        if reject and accepted:
            w_all = sum(h.weight for h in held)
            w_acc = sum(h.weight for h in accepted)
            if w_acc > 0:
                factor = w_all / w_acc
        for h in accepted:
            self._agg.add_client(h.update, h.weight * factor, rank=h.rank)
            self.stats.accepted += 1
        self._held = []


def _delta_norm(update: Dict, init: Optional[Dict]) -> float:
    """Global L2 norm of the update's wire-tensor delta vs the round init
    (or of the raw tensors when no init is known), in float64 — the
    statistic the full-mode MAD quarantine judges."""
    total = 0.0
    for path in adapter_leaf_paths(update):
        leaf = get_path(update, path)
        ref = get_path(init, path) if init is not None else None
        for name in ("A", "B"):
            arr = np.asarray(leaf[name], np.float64)
            if ref is not None:
                arr = arr - np.asarray(ref[name], np.float64)
            total += float(np.sum(arr * arr))
    return math.sqrt(total)


def make_validator(spec: Any = "screen", **cfg) -> ValidationGate:
    """Coerce a gate spec (instance | mode name | None) into a
    :class:`ValidationGate`; an instance is returned as-is."""
    if isinstance(spec, ValidationGate):
        return spec
    return ValidationGate(mode=spec or "off", **cfg)
