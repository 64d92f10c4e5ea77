"""Share of the device's busy time in the traced window spent in the
allocator: ops under the program's ``alloc`` device scope (every
classed-pool op, the step's page plan and page-table write), in percent.
Reads the op metadata of the trace (``chipbench/scopes.py``), kept on
the run as ``run.scopes``; None without it, or when the program names
no device scope."""

from chipbench import scopes, xplane


def read(run):
    sc = getattr(run, "scopes", None)
    if sc is None:
        return None
    ops = scopes.window_ops(sc, run.device, run.lo, run.hi)
    if not scopes.scoped(ops):
        return None
    busy = xplane.busy_ns(run.trace.ops[run.device], run.lo, run.hi)
    return 100.0 * sum(o.dur for o in ops if o.scope == "alloc") / busy
