"""The traffic generator: deterministic per seed, the same work for
every seed, and the stated distributions."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE))

from chipbench import traffic  # noqa: E402

CHAT = json.loads((HERE / "traffic" / "chat_shared.json").read_text())
LONG = json.loads((HERE / "traffic" / "decode_long.json").read_text())
BIG = 2**33 + 12345          # seeds may exceed 32 bits


@pytest.mark.parametrize("mix", [CHAT, LONG], ids=["chat", "long"])
def test_same_seed_same_requests(mix):
    a = traffic.plan(mix, BIG, 50304)
    b = traffic.plan(mix, BIG, 50304)
    assert [(p.prompt, p.max_new, p.gap_s) for p in a] == \
        [(p.prompt, p.max_new, p.gap_s) for p in b]


@pytest.mark.parametrize("mix", [CHAT, LONG], ids=["chat", "long"])
def test_seeds_permute_one_set_of_work(mix):
    a = traffic.plan(mix, 1, 50304)
    b = traffic.plan(mix, 2, 50304)
    assert [p.prompt for p in a] != [p.prompt for p in b]
    assert sorted(p.max_new for p in a) == sorted(p.max_new for p in b)
    assert sorted(p.gap_s for p in a) == sorted(p.gap_s for p in b)
    assert sorted(p.prefix for p in a) == sorted(p.prefix for p in b)


def test_blocks_hold_the_same_work_for_every_seed():
    b = CHAT["block"]
    plans = [traffic.plan(CHAT, s, 50304) for s in (1, 2, BIG)]
    for k in range(0, CHAT["requests"], b):
        blocks = [p[k:k + b] for p in plans]
        for key in ("max_new", "gap_s", "prefix"):
            sets = [sorted(getattr(r, key) for r in blk) for blk in blocks]
            assert sets[0] == sets[1] == sets[2], (k, key)
        pre = [traffic.prefixes(CHAT, s, 50304) for s in (1, 2, BIG)]
        own = [sorted(len(r.prompt) - len(pr[r.prefix]) for r in blk)
               for blk, pr in zip(blocks, pre)]
        assert own[0] == own[1] == own[2]
    # each block spans the same time: the stated rate, block by block
    gaps = np.array([r.gap_s for r in plans[0]]).reshape(-1, b)
    assert np.allclose(gaps.sum(axis=1), b / CHAT["rate_per_s"])
    assert [r.max_new for r in plans[0]] != [r.max_new for r in plans[1]]


def test_blocked_takes_one_value_of_each_stratum():
    v = np.arange(24)
    out = traffic.blocked(v, 4, np.random.default_rng(7)).reshape(-1, 4)
    assert sorted(out.ravel()) == list(v)
    assert all(sorted(row // 6) == [0, 1, 2, 3] for row in out)
    with pytest.raises(ValueError):
        traffic.blocked(np.arange(10), 4, np.random.default_rng(0))


def test_lognormal_quantiles_draw_the_stated_distribution():
    spec = {"dist": "lognormal", "median": 128, "sigma": 0.8,
            "min": 16, "max": 512}
    v = traffic.quantiles(spec, 1001)
    assert v.min() >= 16 and v.max() <= 512
    assert np.median(v) == 128
    # the quartiles of a lognormal: median * exp(+-0.6745 sigma)
    q1, q3 = np.percentile(v, [25, 75])
    assert abs(q1 - 128 * np.exp(-0.6745 * 0.8)) <= 1
    assert abs(q3 - 128 * np.exp(0.6745 * 0.8)) <= 1


def test_uniform_quantiles_span_the_range():
    v = traffic.quantiles({"dist": "uniform", "min": 256, "max": 1024}, 769)
    assert v.min() == 256 and v.max() == 1024
    assert abs(v.mean() - 640) < 1


def test_zipf_counts_follow_one_over_rank():
    c = traffic.zipf_counts(8, 1.0, 2718)
    assert c.sum() == 2718
    w = 1 / np.arange(1, 9)
    assert np.all(np.abs(c - 2718 * w / w.sum()) < 1)


def test_poisson_gaps_have_the_stated_mean():
    g = traffic.exp_gaps(0.5, 4000)
    assert abs(g.mean() - 2.0) < 0.01
    assert abs(np.median(g) - 2.0 * np.log(2)) < 0.01


def test_chat_prompts_start_with_their_shared_prefix():
    pre = traffic.prefixes(CHAT, 5, 50304)
    assert [len(p) for p in pre] == CHAT["prefixes"]["lengths"]
    for p in traffic.plan(CHAT, 5, 50304):
        assert p.prompt[:len(pre[p.prefix])] == pre[p.prefix]
        assert 16 <= len(p.prompt) - len(pre[p.prefix]) <= 512


def test_closed_loop_plan_has_no_schedule():
    assert traffic.concurrency(LONG, 8) == 8
    assert all(p.gap_s == 0 for p in traffic.plan(LONG, 3, 200064))


def test_warmup_serves_every_prefix_then_maps_pins_and_live_pages():
    waves = traffic.warmup(CHAT, 9, 50304, 64)
    assert [live for _, live in waves] == [False, False, True]
    first, second, live = (w for w, _ in waves)
    assert sorted(p.prefix for p in first) == list(range(8))
    assert sorted(p.prefix for p in second) == list(range(8))
    assert live[0].prefix == live[1].prefix
