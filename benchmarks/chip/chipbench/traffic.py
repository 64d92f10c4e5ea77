"""The one traffic generator: reads a mix's parameters and makes the
requests of a run from ``--seed``.

Every seed gets the same *set* of lengths, prefix choices and
inter-arrival gaps, drawn by stratified quantiles of the stated
distributions; the seed permutes their order and draws the token ids
(and, elsewhere, the weights).  So runs with different seeds do the
same amount of work, in another order.  The order is permuted in
blocks of ``block`` consecutive requests: each block holds one value
of every stratum of each quantity and the same gaps, so every block
spans the same time and any stretch of the run (the measured window
too) gets about the same work whatever the seed.

Mix file keys (``traffic/<name>.json``):

``loop``            ``"open"`` (arrivals on a schedule) or ``"closed"``
``rate_per_s``      open loop: mean arrival rate (Poisson gaps)
``concurrency``     closed loop: clients, an integer or ``"b_local"``
``preroll_s``       seconds the load runs before the measured window
                    opens (default 0), so that the window starts in
                    the load's steady state
``requests``        size of the request set, a multiple of ``block``
``block``           requests per block of the order (default: all)
``prefixes``        optional shared prefixes: ``lengths`` (one per
                    prefix) and ``zipf_exponent`` of their popularity
``prompt``          length distribution of each prompt's own tokens
``output``          length distribution of the output
A length distribution is ``{"dist": "lognormal", "median", "sigma",
"min", "max"}`` or ``{"dist": "uniform", "min", "max"}``.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import List, Optional

import numpy as np

_STREAM_TOKENS, _STREAM_ORDER, _STREAM_WARM, _STREAM_BLOCKS = 1, 2, 3, 5


@dataclasses.dataclass
class Planned:
    """One request of a run, and what the harness observed of it."""
    rid: int
    prompt: List[int]
    max_new: int
    prefix: int = -1            # index of its shared prefix, -1 for none
    gap_s: float = 0.0          # open loop: wait after the previous one
    due: float = 0.0            # host perf_counter when it was due
    submitted: float = 0.0      # host perf_counter when it was submitted
    token_times: List[float] = dataclasses.field(default_factory=list)
    handle: object = None       # the program's request object


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy stream of ``seed`` (any non-negative int)."""
    return np.random.default_rng([int(seed), stream])


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified draws (the (i + 1/2)/n quantiles) of a length
    distribution, rounded and clipped to ``[min, max]``."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + u * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(int)


def zipf_counts(k: int, exponent: float, n: int) -> np.ndarray:
    """How many of ``n`` requests go to each of ``k`` items ranked by a
    Zipf law (largest remainder rounding)."""
    w = 1.0 / np.arange(1, k + 1) ** exponent
    share = n * w / w.sum()
    counts = np.floor(share).astype(int)
    rest = n - counts.sum()
    counts[np.argsort(-(share - counts), kind="stable")[:rest]] += 1
    return counts


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` stratified inter-arrival gaps of a Poisson process."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def blocked(values: np.ndarray, block: int,
            g: np.random.Generator) -> np.ndarray:
    """``values`` in blocks of ``block`` consecutive entries.  Which
    values share a block is the same for every seed: each block holds
    one value of each of ``block`` strata (the sorted values cut into
    ``block`` equal slices), spread over the blocks by a fixed draw.
    ``g`` orders each block's values."""
    n = len(values)
    if n % block:
        raise ValueError(f"{n} requests are not a multiple of the block "
                         f"{block}")
    fixed = rng(0, _STREAM_BLOCKS)
    strata = np.sort(values).reshape(block, n // block)
    blocks = np.stack([fixed.permutation(s) for s in strata], axis=1)
    return np.stack([g.permutation(row) for row in blocks]).ravel()


def concurrency(mix: dict, b_local: int) -> Optional[int]:
    c = mix.get("concurrency")
    if c is None:
        return None
    return b_local if c == "b_local" else int(c)


def prefixes(mix: dict, seed: int, vocab: int) -> List[List[int]]:
    """The shared prefixes' tokens (lengths fixed by the mix)."""
    spec = mix.get("prefixes")
    if not spec:
        return []
    g = rng(seed, _STREAM_TOKENS)
    return [list(g.integers(1, vocab - 1, int(n))) for n in spec["lengths"]]


def plan(mix: dict, seed: int, vocab: int) -> List[Planned]:
    """The run's requests in submission order."""
    n = int(mix["requests"])
    order = rng(seed, _STREAM_ORDER)
    tok = rng(seed, _STREAM_TOKENS)
    pre = prefixes(mix, seed, vocab)
    b = int(mix.get("block", n))
    own = blocked(quantiles(mix["prompt"], n), b, order)
    out = blocked(quantiles(mix["output"], n), b, order)
    if pre:
        spec = mix["prefixes"]
        counts = zipf_counts(len(pre), spec.get("zipf_exponent", 1.0), n)
        which = blocked(np.repeat(np.arange(len(pre)), counts), b, order)
    else:
        which = np.full(n, -1)
    if mix["loop"] == "open":
        # every block the same stratified gaps, scaled to the stated mean
        g = exp_gaps(mix["rate_per_s"], b)
        g *= b / mix["rate_per_s"] / g.sum()
        gaps = blocked(np.tile(g, n // b), b, order)
    else:
        gaps = np.zeros(n)
    reqs = []
    for i in range(n):
        p = int(which[i])
        prompt = (list(pre[p]) if p >= 0 else []) + list(
            tok.integers(1, vocab - 1, int(own[i])))
        reqs.append(Planned(rid=i, prompt=[int(t) for t in prompt],
                            max_new=int(out[i]), prefix=p,
                            gap_s=float(gaps[i])))
    return reqs


def warmup(mix: dict, seed: int, vocab: int, chunk: int):
    """Set-up traffic as ``(wave, live_second)`` pairs, served one after
    the other: one request per shared prefix (so the pins are warm, as
    in a deployment), then one more per prefix that maps the pinned
    pages, then two on one prefix where the second is submitted while
    the first decodes and maps its live pages.  A mix without prefixes
    gets one request of a chunk and a bit.  Every request emits a few
    tokens, so both lane widths run."""
    g = rng(seed, _STREAM_WARM)
    pre = prefixes(mix, seed, vocab)

    def req(rid, prefix_tokens, p, extra, max_new=4):
        own = [int(t) for t in g.integers(1, vocab - 1, extra)]
        return Planned(rid=rid, prompt=list(prefix_tokens) + own,
                       max_new=max_new, prefix=p)

    if not pre:
        return [([req(-1, [], -1, chunk + 8)], False)]
    first = [req(-1 - i, t, i, 24) for i, t in enumerate(pre)]
    second = [req(-100 - i, t, i, 24) for i, t in enumerate(pre)]
    live = [req(-200, pre[0], 0, 24, max_new=8), req(-201, pre[0], 0, 24)]
    return [(first, False), (second, False), (live, True)]
