"""Host spans written by the harness around the calls into each layer.

Each span is both a ``jax.profiler.TraceAnnotation`` named ``bench.<name>``
(so a traced run can attribute device idle gaps to it) and a host-clock
record kept in memory.  Spans are set by wrapping methods of the objects the
harness built: the program itself is not edited.
"""
from __future__ import annotations

import contextlib
import functools
import time
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Tuple

import jax


class Spans:
    def __init__(self):
        self.records: Dict[str, List[Tuple[float, float]]] = defaultdict(list)

    @contextlib.contextmanager
    def span(self, name: str):
        with jax.profiler.TraceAnnotation("bench." + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records[name].append((t0, time.perf_counter()))

    def wrap(self, obj, attr: str, name: str,
             ready: Optional[Callable] = None, before: Optional[Callable] = None):
        """Replace ``obj.attr`` by a wrapper that runs it inside the span
        ``name``.  ``before(*args)`` runs first, outside the span (to wait
        for inputs that are still being computed); ``ready(result)`` runs
        inside it (to wait for a result that is computed asynchronously)."""
        fn = getattr(obj, attr)

        @functools.wraps(fn)
        def wrapped(*args, **kw):
            if before is not None:
                before(*args, **kw)
            with self.span(name):
                out = fn(*args, **kw)
                if ready is not None:
                    ready(out)
            return out

        setattr(obj, attr, wrapped)
        return fn

    def total(self, name: str, lo: float = float("-inf"),
              hi: float = float("inf")) -> Tuple[int, float]:
        """(count, seconds) of the spans ``name`` that start in [lo, hi)."""
        iv = [(s, e) for s, e in self.records.get(name, ()) if lo <= s < hi]
        return len(iv), sum(e - s for s, e in iv)
