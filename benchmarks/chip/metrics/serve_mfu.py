"""Model FLOPs of every token the serve steps processed in the traced
window (prefilled tokens not mapped from shared pages, decoded tokens,
and the vocabulary projection of each token emitted; counted by
``chipbench/work.py``) over the window's length times the chip's peak,
in percent."""

from chipbench import traced, work


def read(run):
    steps = [s for s, _ in traced.step_spans(run) if s.dispatched]
    if not steps:
        return None
    flops = work.model_flops(run.dims, [s.lanes for s in steps],
                             sum(s.emitted for s in steps))
    return 100.0 * flops / ((run.hi - run.lo) / 1e9 * run.peak_flops)
