"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device operation intervals, compiled-program executions,
kernel events and the harness's own host spans, all on the trace's one
clock (nanoseconds).

Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:n`` plane; program executions those of its ``XLA
Modules`` line.  Host spans are the ``TraceAnnotation``s the harness
writes (``window``, ``submit``, ``step``, ``record``, ``sleep``).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]            # (start_ns, end_ns)

#: ops that contain other ops: their time is their children's
CONTAINERS = ("%while", "%conditional", "%call", "%async")

HOST_SPANS = ("window", "submit", "step", "record", "sleep")


@dataclasses.dataclass
class Event:
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]          # device plane -> XLA Ops events
    modules: Dict[str, List[Event]]      # device plane -> XLA Modules
    host: List[Event]                    # the harness's host spans

    def devices(self) -> List[str]:
        return sorted(self.ops)

    def span(self, name: str) -> Optional[Event]:
        return next((e for e in self.host if e.name == name), None)

    def spans(self, name: str) -> List[Event]:
        return [e for e in self.host if e.name == name]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = [Event(e.name, e.start_ns,
                                             e.duration_ns)
                                       for e in line.events]
                elif line.name == "XLA Modules":
                    modules[plane.name] = [Event(e.name, e.start_ns,
                                                 e.duration_ns)
                                           for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host.append(Event(e.name, e.start_ns,
                                          e.duration_ns))
    host.sort(key=lambda e: e.start)
    return Trace(ops, modules, host)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals; returns them sorted and disjoint."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Interval]:
    return [(max(e.start, lo), min(e.end, hi)) for e in events
            if e.end > lo and e.start < hi]


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    """Length of the union of the events' intervals inside [lo, hi]."""
    return sum(b - a for a, b in union(clip(events, lo, hi)))


def idle_gaps(events: Sequence[Event], lo: float,
              hi: float) -> List[Interval]:
    """The intervals inside [lo, hi] in which no event runs."""
    gaps, t = [], lo
    for a, b in union(clip(events, lo, hi)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def covering(host: Sequence[Event], t: float,
             names: Sequence[str]) -> str:
    """Name of the innermost harness span (of ``names``) covering ``t``."""
    best = None
    for e in host:
        if e.name in names and e.start <= t <= e.end:
            if best is None or e.dur < best.dur:
                best = e
    return best.name if best else "other"


def op_label(name: str) -> str:
    """``%copy.240 = bf16[16,1,608,...]{...} copy(...)`` -> ``copy.240
    bf16[16,1,608,...]``: the instruction and its result's shape."""
    head, _, rest = name.partition(" = ")
    shape = rest.split("{")[0].split(" ")[0] if rest else ""
    return (head.lstrip("%") + " " + shape).strip()


def leaf_ops(events: Sequence[Event]) -> List[Event]:
    return [e for e in events if not e.name.startswith(CONTAINERS)]


def kernel_events(events: Sequence[Event], kernel: str) -> List[Event]:
    """Device events of one Pallas kernel, by its name."""
    return [e for e in events if e.name.lstrip("%").startswith(kernel)]


def top_ops(events: Sequence[Event], lo: float, hi: float,
            n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` leaf operations that took most device time in [lo, hi],
    as (label, seconds)."""
    tot: Dict[str, float] = {}
    for e in leaf_ops(events):
        if lo <= e.start < hi:
            k = op_label(e.name)
            tot[k] = tot.get(k, 0.0) + e.dur
    return [(k, v / 1e9) for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
