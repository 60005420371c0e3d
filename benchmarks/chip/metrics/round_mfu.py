"""Whole round: client-training model FLOPs of the window's train steps
(``bench.flops.train_step_work``, no recomputation counted) over the window
wall time times the chip's bf16 peak.  Moves ``round_s``."""


def read(ctx):
    work = ctx.get("train_steps") or []
    if not work or ctx.get("peaks") is None:
        return None
    lo, hi = ctx["window"]
    ops = sum(o for o, _ in work)
    return ops / ((hi - lo) * ctx["peaks"]["bf16_flops"]) * 100.0
