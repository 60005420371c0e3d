"""Client train step: the roofline's least time for the window's train
steps (the larger of operations over peak FLOP/s and bytes over HBM
bandwidth, from ``bench.flops.train_step_work``; compute bounds every
step of these cells) over their device time.  Moves ``round_s``."""
from bench import flops


def read(ctx):
    runs, secs = ctx["trace"].device_seconds("jit_train_step")
    work = ctx.get("train_steps") or []
    if not runs or runs != len(work) or ctx.get("peaks") is None:
        return None
    least = sum(flops.least_time(o, b, ctx["peaks"])[0] for o, b in work)
    return least / secs * 100.0
