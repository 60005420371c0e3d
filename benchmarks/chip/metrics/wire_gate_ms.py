"""Transport and validation gate (``core/runtime/transport.py``,
``validation.py``): host time per round in the harness's ``wire`` spans
(upload encoding and decoding with the CRC) and ``gate`` spans (screening
and the fold into the aggregator).  Moves ``round_s``."""


def read(ctx):
    lo, hi = ctx["window"]
    _, wire = ctx["spans"].total("wire", lo, hi)
    _, gate = ctx["spans"].total("gate", lo, hi)
    return (wire + gate) / ctx["rounds"] * 1e3 if ctx.get("rounds") else None
