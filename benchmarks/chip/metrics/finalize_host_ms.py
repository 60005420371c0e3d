"""FLoRIST server (``core/aggregators/florist.py``): host time per round in
the program's ``finalize`` span less its ``finalize.wait`` (the settle and
core dispatches, and the truncation of the global adapters).  Moves
``round_s``."""
from metrics import _telemetry


def read(ctx):
    return _telemetry.per_round_ms(ctx, ("finalize",), less=("finalize.wait",))
