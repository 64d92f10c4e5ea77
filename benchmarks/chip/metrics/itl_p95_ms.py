"""95th percentile of every gap between consecutive output tokens of
every request, both tokens inside the window."""

from chipbench import stats


def read(run):
    v = stats.percentile(stats.itl_samples(run.reqs, run.t0, run.end), 95)
    return None if v is None else v * 1e3
