"""Client runner and train step (``core/runtime/runners.py``): host time
per round in the program's ``client.init`` (the clients' starting adapters),
``client.batches`` (batch draws and their copies to the device) and
``client.train`` (dispatch of the train steps) spans.  Moves ``round_s``."""
from metrics import _telemetry


def read(ctx):
    return _telemetry.per_round_ms(
        ctx, ("client.init", "client.batches", "client.train"))
