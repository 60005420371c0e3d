"""Operations and bytes of the algorithm, computed from shapes.

These never come from ``cost_analysis`` of a compiled program: a roofline
share or an MFU must read the same work whatever implements it.  The counts
of a block live with its family (``bench/families/<model_type>.py``:
``Dims`` and ``train_step_work``), under the conventions here.

Conventions: a multiply-add is 2 operations; attention is causal, so a
sequence of ``S`` tokens scores ``S (S + 1) / 2`` query-key pairs; weights
are bf16 (2 bytes) and LoRA optimizer state is fp32.
"""
from __future__ import annotations

from typing import Tuple

BF16 = 2
FP32 = 4


def least_time(ops: float, nbytes: float, peaks: dict) -> Tuple[float, str]:
    """The roofline's least time for the work and which bound sets it."""
    t_c = ops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
