"""Device time of the serve-step program in steps whose every lane is
one token wide, from the trace: in each such step's host span, the
longest program execution; their mean."""

from chipbench import traced


def read(run):
    times = [traced.serve_program_ns(run, span) for s, span in
             traced.step_spans(run) if s.dispatched and s.width1]
    times = [t for t in times if t]
    return sum(times) / len(times) / 1e6 if times else None
