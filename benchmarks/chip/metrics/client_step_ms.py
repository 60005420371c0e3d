"""Client runner and train step (``core/runtime/runners.py``,
``train/step.py``): device time per client train step, from the trace's
``jit_train_step`` module events in the window.  Moves ``round_s``."""


def read(ctx):
    runs, secs = ctx["trace"].device_seconds("jit_train_step")
    return secs / runs * 1e3 if runs else None
