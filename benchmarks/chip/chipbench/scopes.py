"""The program's named device scopes and host phases, from a profiler
trace (``.xplane.pb``): what :mod:`chipbench.xplane` leaves out.

``jax.profiler.ProfileData`` gives each device operation's name and
times, not the metadata the compiler attached to it.  That metadata is
in the XPlane's event metadata: ``tf_op``, the op's JAX name stack with
the program's ``jax.named_scope``s in it, and ``shape_with_layout``, its
result's shape.  This module parses it with the XPlane protobuf module
of the installed TensorFlow wheel, loaded by its file so that
TensorFlow itself is not imported.

The names are the program's closed sets (``serving/trace.py``): device
scopes :data:`SCOPES`, host phases named ``serve.*``.  An op belongs to
its innermost scope; an op with no ``tf_op`` (a copy the compiler
added) to :data:`NONE`, one under no scope to :data:`UNSCOPED`.  A
trace of a program without scopes or phases gives every op
:data:`UNSCOPED` or :data:`NONE` and no phases, and the readers here
then return None.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import re
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

from . import xplane

#: the program's device scopes (``DEVICE_SCOPES`` in serving/trace.py)
SCOPES = ("alloc", "kv_write", "kv_layout", "attn", "ffn", "head",
          "layers")
#: scopes whose ops only move the KV pool: ``kv_layout``, and the layer
#: scan's own slicing and write-back, which carry no inner scope
RELAYOUT = ("kv_layout", "layers")
PHASE_PREFIX = "serve."
NONE = "(none)"
UNSCOPED = "(unscoped)"


@dataclasses.dataclass
class Op:
    name: str
    start: float
    dur: float
    scope: str
    shape: str                   # result type without layout

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Scoped:
    ops: Dict[str, List[Op]]     # device plane -> its leaf XLA ops
    phases: List[xplane.Event]   # host spans named serve.*, by start


def _xplane_pb2():
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        raise ImportError("no XPlane protobuf module: TensorFlow's wheel "
                          "is not installed")
    path = (Path(spec.submodule_search_locations[0]) / "tsl" / "profiler"
            / "protobuf" / "xplane_pb2.py")
    mod_spec = importlib.util.spec_from_file_location(
        "chipbench_xplane_pb2", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def scope_of(tf_op: str) -> str:
    """Innermost scope of :data:`SCOPES` in a ``tf_op`` such as
    ``jit(serve_step)/layers/while/body/attn/kv_layout/transpose:``.
    A scope is a whole component of the name stack: inside a
    transform's parentheses stands a function's name (``vmap(alloc)``
    is a function ``alloc`` under ``vmap``), and the program opens no
    scope inside a transform."""
    if not tf_op:
        return NONE
    for part in reversed(tf_op.rsplit(":", 1)[0].split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def _stat_str(stat, names: Dict[int, str]) -> str:
    kind = stat.WhichOneof("value")
    if kind == "ref_value":
        return names.get(stat.ref_value, "")
    return stat.str_value if kind == "str_value" else ""


def load(path: str) -> Scoped:
    pb = _xplane_pb2()
    space = pb.XSpace()
    space.ParseFromString(Path(path).read_bytes())
    ops: Dict[str, List[Op]] = {}
    phases: List[xplane.Event] = []
    for plane in space.planes:
        names = {k: m.name for k, m in plane.stat_metadata.items()}
        if plane.name.startswith("/device:TPU:"):
            meta = {}
            for k, em in plane.event_metadata.items():
                st = {names.get(s.metadata_id): _stat_str(s, names)
                      for s in em.stats}
                shape = re.sub(r"\{[^}]*\}", "",
                               st.get("shape_with_layout", ""))
                meta[k] = (em.name, scope_of(st.get("tf_op", "")), shape)
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                # times as ProfileData gives them, whole nanoseconds
                ops[plane.name] = [
                    Op(name, line.timestamp_ns + e.offset_ps // 1000,
                       e.duration_ps // 1000, scope, shape)
                    for e in line.events
                    for name, scope, shape in (meta[e.metadata_id],)
                    if not name.startswith(xplane.CONTAINERS)]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    em = plane.event_metadata.get(e.metadata_id)
                    if em is not None and em.name.startswith(PHASE_PREFIX):
                        phases.append(xplane.Event(
                            em.name,
                            line.timestamp_ns + e.offset_ps // 1000,
                            e.duration_ps // 1000))
    phases.sort(key=lambda e: e.start)
    return Scoped(ops, phases)


def window_ops(sc: Scoped, device: str, lo: float, hi: float) -> List[Op]:
    return [o for o in sc.ops.get(device, []) if lo <= o.start < hi]


def device_scopes(sc: Scoped, device: str, lo: float,
                  hi: float) -> Dict[str, float]:
    """Device seconds of the ops that started in [lo, hi), by innermost
    scope, largest first."""
    tot: Dict[str, float] = {}
    for o in window_ops(sc, device, lo, hi):
        tot[o.scope] = tot.get(o.scope, 0.0) + o.dur
    return {k: v / 1e9
            for k, v in sorted(tot.items(), key=lambda kv: -kv[1])}


def scoped(ops: Sequence[Op]) -> bool:
    """Whether the program named any scope in these ops."""
    return any(o.scope in SCOPES for o in ops)


def relayout_ns(ops: Sequence[Op]) -> float:
    """Device time that only moves the KV pool: ops under ``kv_layout``,
    ops of the layer scan with no inner scope, and ops with no ``tf_op``
    whose result has the shape of one of those (the whole-pool copies
    the compiler adds), the shapes taken from the same ops."""
    moved = [o for o in ops if o.scope in RELAYOUT]
    shapes = {o.shape for o in moved}
    return sum(o.dur for o in moved) + sum(
        o.dur for o in ops if o.scope == NONE and o.shape in shapes)


def host_step_ns(phases: Sequence[xplane.Event], lo: float,
                 hi: float) -> List[float]:
    """For each ``serve.step`` that started in [lo, hi) and holds a
    ``serve.sync`` (a step that dispatched): its length less the
    sync's, the host's own time in the step."""
    syncs = [p for p in phases if p.name == "serve.sync"]
    out = []
    for s in phases:
        if s.name == "serve.step" and lo <= s.start < hi:
            inner = [p for p in syncs if s.start <= p.start < s.end]
            if inner:
                out.append(s.dur - sum(p.dur for p in inner))
    return out


def label_gaps(host: Sequence[xplane.Event],
               phases: Sequence[xplane.Event],
               gaps: Sequence[Tuple[float, float]],
               names: Sequence[str]) -> List[list]:
    """``[name, seconds]`` per idle gap: the innermost span covering its
    middle, of the harness's ``names`` and the program's phases."""
    spans = sorted(list(host) + list(phases), key=lambda e: e.start)
    every = tuple(names) + tuple({p.name for p in phases})
    return [[xplane.covering(spans, (a + b) / 2, every), (b - a) / 1e9]
            for a, b in gaps]


def idle_by_phase(phases: Sequence[xplane.Event],
                  gaps: Sequence[Tuple[float, float]]) -> Dict[str, float]:
    """Device idle seconds split, instant by instant, by the innermost
    phase covering it (a gap that spans the end of ``serve.sync``,
    ``serve.post`` and the next step's dispatch is divided among them,
    not named by its middle); idle under no phase is ``other``."""
    out: Dict[str, float] = {}
    for a, b in gaps:
        over = [p for p in phases if p.start < b and p.end > a]
        cuts = sorted({a, b} | {t for p in over for t in (p.start, p.end)
                                if a < t < b})
        for x, y in zip(cuts, cuts[1:]):
            mid = (x + y) / 2
            inner = [p for p in over if p.start <= mid <= p.end]
            name = min(inner, key=lambda p: p.dur).name if inner else "other"
            out[name] = out.get(name, 0.0) + (y - x) / 1e9
    return out


def step_idle_uncovered(phases: Sequence[xplane.Event],
                        gaps: Sequence[Tuple[float, float]]
                        ) -> Tuple[float, float]:
    """(device idle ns inside ``serve.step`` spans, the part of it that
    no phase inside the step covers)."""
    steps = [p for p in phases if p.name == "serve.step"]
    inner = xplane.union([(p.start, p.end) for p in phases
                          if p.name != "serve.step"])
    idle = uncovered = 0.0
    for a, b in gaps:
        for s in steps:
            lo, hi = max(a, s.start), min(b, s.end)
            if hi <= lo:
                continue
            idle += hi - lo
            covered = sum(min(hi, y) - max(lo, x) for x, y in inner
                          if y > lo and x < hi)
            uncovered += hi - lo - covered
    return idle, uncovered
