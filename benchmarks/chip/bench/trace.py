"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

``load_events`` keeps what the reduction reads: the device planes'
``XLA Modules`` (one event per executable run) and ``XLA Ops`` lines, and
the host spans the harness writes (``TraceAnnotation`` names starting with
``bench.``).  ``reduce_events`` then computes, over the traced window (the
host span ``bench.window``):

* the busy time: the union of the intervals in which an operation ran on a
  device, averaged over the devices used;
* device time per executable (``jit_train_step``, ``jit_step``, ...), from
  the module events, with their counts;
* the device operations that took most time, each by its exclusive time
  (a loop's body counts for the operations in it, not for the loop);
* the longest idle gaps, each named after the harness span that overlapped
  it most (what the host was doing meanwhile).

Device and host timestamps share the trace's clock to within about a
millisecond, which is below the gaps this attributes.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = SPAN_PREFIX + "window"
DEVICE_LINES = ("XLA Modules", "XLA Ops")

# one event: (kind, device, name, start_ns, end_ns); kind is "module",
# "op" or "span"
Event = Tuple[str, int, str, float, float]


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load_events(xplane_path: str) -> List[Event]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    out: List[Event] = []
    for plane in data.planes:
        m = re.match(r"/device:[A-Z]+:(\d+)$", plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name not in DEVICE_LINES:
                    continue
                kind = "module" if line.name == "XLA Modules" else "op"
                for e in line.events:
                    out.append((kind, dev, e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        out.append(("span", -1, e.name[len(SPAN_PREFIX):],
                                    e.start_ns, e.start_ns + e.duration_ns))
    return out


def executable_name(module_event_name: str) -> str:
    """``jit_train_step(1234)`` -> ``jit_train_step``."""
    return re.sub(r"\(\d+\)$", "", module_event_name)


def op_name(op_event_name: str) -> str:
    """``%fusion.12 = bf16[..] fusion(..)`` -> ``fusion``: the HLO
    instruction name without its number, so that one kind of operation is
    summed over its instances."""
    head = op_event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[.-]\d+$", "", head)


def merge(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(ops: Sequence[Tuple[str, float, float]]) -> List[Tuple[str, float]]:
    """(name, exclusive time) of each operation event of one device: its
    duration less the time of the events that start inside it (a while
    loop less its body), so that the times sum to the busy time."""
    out: List[Tuple[str, float]] = []
    stack: List[list] = []        # [name, start, end, time nested inside]
    for n, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= s:
            top = stack.pop()
            out.append((top[0], top[2] - top[1] - top[3]))
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([n, s, e, 0.0])
    out += [(n, e - s - nested) for n, s, e, nested in stack]
    return out


def _clip(s: float, e: float, lo: float, hi: float) -> Optional[Tuple[float, float]]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # averaged over devices
    devices: int
    executables: Dict[str, Tuple[int, float]] = field(default_factory=dict)
    top_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)
    spans: Dict[str, List[float]] = field(default_factory=dict)

    def device_seconds(self, executable: str) -> Tuple[int, float]:
        """(runs, device seconds) of one executable in the window."""
        return self.executables.get(executable, (0, 0.0))


def reduce_events(events: Sequence[Event], top: int = 10) -> TraceSummary:
    wins = [(s, e) for k, _, n, s, e in events if k == "span" and n == "window"]
    if len(wins) != 1:
        raise ValueError(f"expected one '{WINDOW_SPAN}' span, found {len(wins)}")
    lo, hi = wins[0]
    devs = sorted({d for k, d, *_ in events if k in ("op", "module")})
    busy_total = 0.0
    busy0: List[List[float]] = []
    for d in devs:
        ops = [(s, e) for k, dd, _, s, e in events if k == "op" and dd == d]
        if not ops:      # a device line without ops: fall back to modules
            ops = [(s, e) for k, dd, _, s, e in events
                   if k == "module" and dd == d]
        clipped = [c for c in (_clip(s, e, lo, hi) for s, e in ops) if c]
        merged = merge(clipped)
        busy_total += sum(e - s for s, e in merged)
        if d == devs[0]:
            busy0 = merged
    exes: Dict[str, List[float]] = {}
    for k, d, n, s, e in events:
        if k == "module" and d == (devs[0] if devs else 0) and lo <= s < hi:
            acc = exes.setdefault(executable_name(n), [0, 0.0])
            acc[0] += 1
            acc[1] += (e - s) * 1e-9
    ops_t: Dict[str, float] = {}
    ops0 = [(op_name(n), *c) for k, d, n, s, e in events
            if k == "op" and d == (devs[0] if devs else 0)
            for c in [_clip(s, e, lo, hi)] if c]
    for n, t in self_times(ops0):
        ops_t[n] = ops_t.get(n, 0.0) + t * 1e-9
    spans: Dict[str, List[float]] = {}
    span_iv = []
    for k, _, n, s, e in events:
        if k == "span" and n != "window":
            spans.setdefault(n, []).append((e - s) * 1e-9)
            span_iv.append((n, s, e))
    edges = [lo] + [x for iv in busy0 for x in iv] + [hi]
    gaps = []
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        best, best_ov = "no harness span", 0.0
        for n, ss, se in span_iv:
            ov = min(e, se) - max(s, ss)
            if ov > best_ov:
                best, best_ov = n, ov
        gaps.append((best, (e - s) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary(
        window_s=(hi - lo) * 1e-9,
        busy_s=(busy_total / len(devs)) * 1e-9 if devs else 0.0,
        devices=len(devs),
        executables={n: (int(c), t) for n, (c, t) in exes.items()},
        top_ops=sorted(ops_t.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=gaps[:top],
        spans=spans)
