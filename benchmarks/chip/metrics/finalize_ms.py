"""FLoRIST server (``core/aggregators/florist.py``, ``core/svd.py``): host
time per round in the harness's ``finalize`` span, which ends when the
global adapters are ready on the device.  Moves ``round_s``."""


def read(ctx):
    lo, hi = ctx["window"]
    n, secs = ctx["spans"].total("finalize", lo, hi)
    return secs / n * 1e3 if n else None
