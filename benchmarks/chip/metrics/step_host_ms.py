"""The host's own time in a dispatching engine step: for each of the
program's ``serve.step`` phase spans in the traced window that holds a
``serve.sync``, its length less the sync's; their mean.  None without
``run.scopes``, or when the program writes no phases."""

from chipbench import scopes


def read(run):
    sc = getattr(run, "scopes", None)
    if sc is None:
        return None
    own = scopes.host_step_ns(sc.phases, run.lo, run.hi)
    return sum(own) / len(own) / 1e6 if own else None
