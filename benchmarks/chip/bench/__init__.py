"""Shared code of the chip benchmark: device and peaks, operation and byte
counts from shapes, trace reduction, host spans, traffic generation and the
comparisons that decide ``correct``.  Nothing here is specific to one cell:
configurations, traffic mixes, drivers and per-layer metric readers live in
files of their own and are found by name, and so does each model family's
block layout (``families/<model_type>.py``)."""
