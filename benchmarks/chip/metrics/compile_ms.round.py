"""Whole round: compile time per round (tracing, lowering and backend
compiles, from the program's compile counter) charged to the program's
spans in the window.  Moves ``round_s``."""
from metrics import _telemetry


def read(ctx):
    return _telemetry.compile_ms(ctx)
