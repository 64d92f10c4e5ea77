"""Host wall time of ``ServingEngine.step`` calls that dispatched device
work in the window, from the harness's own clock around each call:
their total over their number."""


def read(run):
    steps = [s for s in run.steps if s.dispatched]
    if not steps:
        return None
    return sum(s.b - s.a for s in steps) / len(steps) * 1e3
