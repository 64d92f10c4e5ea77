"""Helpers the trace-reading metrics share: the harness's step records
paired with their host spans in the trace."""

from __future__ import annotations

from typing import List, Tuple


def step_spans(run) -> List[Tuple[object, object]]:
    """(step record, its ``step`` span) for every step the window ran.
    The k-th ``step`` span that starts in the window is the k-th step
    call of the window; an unequal count pairs nothing."""
    spans = [e for e in run.trace.spans("step") if run.lo <= e.start < run.hi]
    steps = [s for s in run.steps if run.t0 <= s.a < run.end]
    if len(spans) != len(steps):
        return []
    return list(zip(steps, spans))


def serve_program_ns(run, span) -> float:
    """Duration of the longest program execution that started in a
    step's span: the serve step (its other programs are scalar updates)."""
    mods = [m.dur for m in run.trace.modules[run.device]
            if span.start <= m.start <= span.end]
    return max(mods, default=0.0)
