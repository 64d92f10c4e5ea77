"""Admission wait, 90th percentile over the requests due in the window:
from when a request was due to the program's ``admit`` trace instant
(``serving/trace.py``); one not admitted by the window's end counts with
the time it had waited by then."""

from chipbench import stats


def read(run):
    admitted = {}
    for e in run.events:
        if e["name"] == "admit":
            admitted.setdefault(e["tid"], e["t"])
    waits = [min(admitted.get(p.rid, run.end), run.end) - p.due
             for p in run.reqs if run.t0 <= p.due < run.end]
    v = stats.percentile(waits, 90)
    return None if v is None else v * 1e3
