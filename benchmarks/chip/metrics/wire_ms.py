"""Transport (``core/runtime/transport.py``): host time per round in the
program's ``wire.up`` spans (uplink: pack with one device fetch, encode,
CRC, decode) and ``wire.down`` span (the downlink of the global adapters).
Moves ``round_s``."""
from metrics import _telemetry


def read(ctx):
    return _telemetry.per_round_ms(ctx, ("wire.up", "wire.down"))
