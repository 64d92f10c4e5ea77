"""Time to first token, 50th percentile over the requests due in the
window, from when each was due; one still without a token at the
window's end counts with the time it had waited by then."""

from chipbench import stats


def read(run):
    v = stats.percentile(stats.ttft_samples(run.reqs, run.t0, run.end), 50)
    return None if v is None else v * 1e3
