"""Host spans and a compile counter for the program's layers.

A span marks what the host does inside one layer of the system::

    with telemetry.span("wire.up", client=k) as s:
        ...
        s.set(bytes=n)

It is a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``, so a
profiler trace shows it on the host plane, on the same clock as the device
operations, and a record in the process's :class:`Recorder`, timed with
``time.perf_counter``: ``(name, start, end, parent, round, attrs)``.
``parent`` is the name of the enclosing span on the same thread; ``round``
is the ``round`` attribute of the nearest enclosing span that has one (the
trainer opens ``round`` around each federated round).  A span adds no sync:
where it ends before the device has finished the work it dispatched, it
measured what the host did, and a device trace shows the rest.

The compile counter listens to JAX's monitoring events, which JAX records
synchronously on the thread that dispatched the call being compiled.  Every
``/jax/core/compile/*`` duration (tracing, lowering to MLIR, and the backend
compile, which also covers a read from the persistent compilation cache) is
charged to the innermost span open on that thread, or to ``(none)``; each
backend-compile event counts one executable.

The recorder is always on.  It keeps the latest ``MAX_RECORDS`` records and
counts those it dropped; the queries take the spans that start in
``[lo, hi)`` on the ``perf_counter`` clock, and ``complete(lo)`` says
whether any record from that range was dropped.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax

#: records each deque keeps (a federated round opens about 50 spans)
MAX_RECORDS = 65536
#: the span a compile is charged to when none is open
NO_SPAN = "(none)"
PREFIX = "repro."

_COMPILE_EVENTS = "/jax/core/compile/"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_INF = float("inf")


class Span(NamedTuple):
    name: str
    start: float
    end: float
    parent: Optional[str]
    round: Optional[int]
    attrs: Dict[str, Any]


class Compile(NamedTuple):
    span: str             # innermost open span, or NO_SPAN
    start: float          # that span's start, or the event's own start
    end: float            # when the event ended
    seconds: float
    executables: int      # 1 for a backend compile, 0 for tracing/lowering
    function: str         # the compiled function's name


class _Open:
    """The context manager :meth:`Recorder.span` returns; ``set`` adds
    attributes known only inside the span (a payload's size)."""

    __slots__ = ("rec", "name", "attrs", "round", "parent", "start", "end",
                 "_ann", "_stack")

    def __init__(self, rec: "Recorder", name: str, attrs: Dict[str, Any]):
        self.rec, self.name, self.attrs = rec, name, attrs
        self.start = self.end = 0.0

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)
        self._ann.set_metadata(**attrs)

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def __enter__(self) -> "_Open":
        stack = self._stack = self.rec._stack()
        top = stack[-1] if stack else None
        self.parent = top.name if top is not None else None
        self.round = self.attrs.get("round", top.round if top else None)
        self._ann = jax.profiler.TraceAnnotation(PREFIX + self.name,
                                                 **self.attrs)
        self._ann.__enter__()
        stack.append(self)
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        self._stack.pop()
        self._ann.__exit__(*exc)
        self.rec._keep(self.rec.spans, Span(
            self.name, self.start, self.end, self.parent, self.round,
            self.attrs))


class Recorder:
    """Span and compile records of one process (see the module doc)."""

    def __init__(self, maxlen: int = MAX_RECORDS):
        self.spans: collections.deque = collections.deque(maxlen=maxlen)
        self.compile_events: collections.deque = collections.deque(
            maxlen=maxlen)
        self.dropped = 0
        self._horizon = -_INF          # latest start among dropped records
        self._lock = threading.Lock()
        self._local = threading.local()
        self._listening = False

    # -- recording -------------------------------------------------------------
    def span(self, name: str, **attrs) -> _Open:
        if not self._listening:
            self._listen()
        return _Open(self, name, attrs)

    def _listen(self) -> None:
        """Register the compile listener (once)."""
        with self._lock:
            if self._listening:
                return
            self._listening = True
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _keep(self, dq: collections.deque, record) -> None:
        if len(dq) == dq.maxlen:
            with self._lock:
                if len(dq) == dq.maxlen:
                    self.dropped += 1
                    self._horizon = max(self._horizon, dq.popleft().start)
        dq.append(record)

    def _on_event(self, event: str, secs: float, **kw) -> None:
        if not event.startswith(_COMPILE_EVENTS):
            return
        end = time.perf_counter()
        stack = self._stack()
        if stack:
            name, start = stack[-1].name, stack[-1].start
        else:
            name, start = NO_SPAN, end - secs
        self._keep(self.compile_events, Compile(
            name, start, end, secs, int(event == _BACKEND_COMPILE),
            str(kw.get("fun_name", ""))))

    # -- queries -----------------------------------------------------------------
    def complete(self, lo: float) -> bool:
        """True if no record that starts at or after ``lo`` was dropped."""
        return self._horizon < lo

    def records(self, name: Optional[str] = None, lo: float = -_INF,
                hi: float = _INF) -> list:
        """Span records (of ``name``, if given) that start in [lo, hi)."""
        return [s for s in list(self.spans) if lo <= s.start < hi
                and (name is None or s.name == name)]

    def total(self, name: str, lo: float = -_INF,
              hi: float = _INF) -> Tuple[int, float]:
        """(count, seconds) of the spans ``name`` that start in [lo, hi)."""
        rs = self.records(name, lo, hi)
        return len(rs), sum(s.end - s.start for s in rs)

    def self_time(self, name: str, lo: float = -_INF,
                  hi: float = _INF) -> float:
        """Seconds in the spans ``name`` that start in [lo, hi), less the
        spans directly inside them."""
        rs = self.records(None, lo, hi)
        return (sum(s.end - s.start for s in rs if s.name == name)
                - sum(s.end - s.start for s in rs if s.parent == name))

    def compiles(self, lo: float = -_INF,
                 hi: float = _INF) -> Dict[str, Tuple[int, float]]:
        """{innermost span name: (executables, seconds)} of the compiles
        charged to spans that start in [lo, hi) (with no span open: that
        started in it)."""
        out: Dict[str, Tuple[int, float]] = {}
        for c in list(self.compile_events):
            if lo <= c.start < hi:
                n, secs = out.get(c.span, (0, 0.0))
                out[c.span] = (n + c.executables, secs + c.seconds)
        return out


#: the process's recorder
RECORDER = Recorder()
span = RECORDER.span
complete = RECORDER.complete
records = RECORDER.records
total = RECORDER.total
self_time = RECORDER.self_time
compiles = RECORDER.compiles
