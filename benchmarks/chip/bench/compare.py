"""Comparison arithmetic for ``correct`` (gaps against the float32
reference, as the repository's bring-up check measures them) and the
record of each number compared beside its limit."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterable, Optional

import numpy as np


def norm(x) -> float:
    return float(np.linalg.norm(np.asarray(x, np.float64).ravel()))


def norm_gaps(prog: Dict, ref: Dict, exclude=()) -> Dict:
    """Per leaf: |‖prog‖ - ‖ref‖| over max(‖ref‖ of the leaf, ‖ref‖ of the
    median leaf).  Leaves in ``exclude`` are left out."""
    refn = {k: norm(v) for k, v in ref.items()}
    med = float(np.median(list(refn.values()))) if refn else 0.0
    out = {}
    for k, v in ref.items():
        if k in exclude:
            continue
        den = max(refn[k], med)
        out[k] = abs(norm(prog[k]) - refn[k]) / den if den > 0 else 0.0
    return out


def worst(a: float, b: float) -> float:
    """The larger of two readings; NaN (a reading that could not be made)
    wins, so it fails its limit."""
    if not (np.isfinite(a) and np.isfinite(b)):
        return float("nan")
    return max(float(a), float(b))


def rel(a: float, b: float) -> float:
    return abs(float(a) - float(b)) / max(abs(float(b)), 1e-30)


@dataclass
class Check:
    name: str
    value: float
    limit: Optional[float]

    @property
    def ok(self) -> bool:
        return (self.limit is not None and np.isfinite(self.value)
                and self.value <= self.limit)


def load_limits(workload: str) -> Dict[str, float]:
    import json
    import os
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(here, "limits", workload + ".json")
    with open(path) as f:
        return {k: float(v) for k, v in json.load(f)["limits"].items()}


def verdict(checks: Iterable[Check]) -> bool:
    checks = list(checks)
    return bool(checks) and all(c.ok for c in checks)
