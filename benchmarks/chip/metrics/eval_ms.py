"""Eval (``core/federated.py``): time per round in the program's ``merge``
span (the global adapters folded into the base) and ``eval`` span (the eval
step and the reads of its loss and accuracy, which wait for it).  Moves
``round_s``."""
from metrics import _telemetry


def read(ctx):
    return _telemetry.per_round_ms(ctx, ("merge", "eval"))
