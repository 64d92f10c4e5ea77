"""Percentiles, censored latencies and rates give known values."""

import sys
from pathlib import Path
from types import SimpleNamespace as R

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE))

from chipbench import stats  # noqa: E402


@pytest.mark.parametrize("q", [0, 10, 50, 90, 95, 100])
def test_percentile_matches_numpy_linear(q):
    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0, 8.0]
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_of_nothing_is_none():
    assert stats.percentile([], 50) is None


def test_ttft_counts_a_request_without_a_token_by_its_wait():
    reqs = [R(due=1.0, token_times=[1.5, 1.6]),     # 0.5
            R(due=2.0, token_times=[]),             # censored: 10 - 2
            R(due=3.0, token_times=[11.0]),         # after the end: 7
            R(due=0.5, token_times=[0.6]),          # due before t0
            R(due=10.0, token_times=[10.2])]        # due at the end
    assert stats.ttft_samples(reqs, 1.0, 10.0) == [0.5, 8.0, 7.0]
    assert stats.percentile(stats.ttft_samples(reqs, 1.0, 10.0), 50) == 7.0


def test_itl_takes_gaps_with_both_tokens_in_the_window():
    reqs = [R(token_times=[0.5, 1.0, 1.5, 2.5, 11.0]),
            R(token_times=[2.0, 2.0, 2.25])]
    assert stats.itl_samples(reqs, 1.0, 10.0) == [0.5, 1.0, 0.0, 0.25]


def test_tokens_in_counts_tokens_inside_the_window():
    reqs = [R(token_times=[0.5, 1.0, 9.0, 10.5]), R(token_times=[5.0])]
    assert stats.tokens_in(reqs, 1.0, 10.0) == 3
