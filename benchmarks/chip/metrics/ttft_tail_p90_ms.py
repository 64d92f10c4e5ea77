"""Time to first token, 90th percentile over the requests due in the
window, from when each was due; one still without a token at the
window's end counts with the time it had waited by then.  With about
28 requests due in a window it turns on whether about the third longest
wait met an evicted system prompt, so it is read beside the end-to-end
median rather than held to a bound."""

from chipbench import stats


def read(run):
    v = stats.percentile(stats.ttft_samples(run.reqs, run.t0, run.end), 90)
    return None if v is None else v * 1e3
