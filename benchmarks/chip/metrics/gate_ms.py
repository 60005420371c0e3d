"""Validation gate (``core/runtime/validation.py``): host time per round in
the program's ``gate`` spans (structure and finite screens, and the fold
into the aggregator).  Moves ``round_s``."""
from metrics import _telemetry


def read(ctx):
    return _telemetry.per_round_ms(ctx, ("gate",))
