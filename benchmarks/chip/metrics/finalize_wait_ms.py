"""FLoRIST server (``core/aggregators/florist.py``): time per round in the
program's ``finalize.wait`` span, the one fetch of spectra and kept ranks,
which waits for the cores on the device and the work queued before them.
Moves ``round_s``."""
from metrics import _telemetry


def read(ctx):
    return _telemetry.per_round_ms(ctx, ("finalize.wait",))
