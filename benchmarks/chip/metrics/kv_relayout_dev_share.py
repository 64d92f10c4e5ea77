"""Share of the device's busy time in the traced window spent moving
the KV pool rather than reading it: ops under the program's
``kv_layout`` device scope, ops of the layer scan with no inner scope,
and ops with no ``tf_op`` whose result has the shape of one of those
(``chipbench/scopes.py`` ``relayout_ns``), in percent.  None without
``run.scopes``, or when the program names no device scope."""

from chipbench import scopes, xplane


def read(run):
    sc = getattr(run, "scopes", None)
    if sc is None:
        return None
    ops = scopes.window_ops(sc, run.device, run.lo, run.hi)
    if not scopes.scoped(ops):
        return None
    busy = xplane.busy_ns(run.trace.ops[run.device], run.lo, run.hi)
    return 100.0 * scopes.relayout_ns(ops) / busy
