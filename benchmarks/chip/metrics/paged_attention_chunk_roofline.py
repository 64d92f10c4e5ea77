"""Share of its roofline that the ``paged_attention_chunk`` kernel
reaches in the window: the least time its work could take on this chip
(per call the larger of FLOPs over peak and bytes over bandwidth, the
work counted from the lanes' live context lengths by
``chipbench/work.py``), over the summed device time of its events."""

from chipbench import traced, work, xplane


def read(run):
    ev = [e for e in xplane.kernel_events(run.trace.ops[run.device],
                                          "paged_attention_chunk")
          if run.lo <= e.start < run.hi]
    spent = sum(e.dur for e in ev) / 1e9
    steps = [s.lanes for s, _ in traced.step_spans(run) if s.dispatched]
    if not spent or not steps:
        return None
    least, _ = work.attn_least_s(run.dims, steps, run.peak_flops,
                                 run.peak_bw)
    return 100.0 * least / spent
