"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / window."""

from chipbench import xplane


def read(run):
    busy = xplane.busy_ns(run.trace.ops[run.device], run.lo, run.hi)
    return 1.0 - busy / (run.hi - run.lo)
