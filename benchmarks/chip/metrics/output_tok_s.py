"""Output tokens that appeared in the window, over the window's length."""

from chipbench import stats


def read(run):
    return stats.tokens_in(run.reqs, run.t0, run.end) / run.seconds
