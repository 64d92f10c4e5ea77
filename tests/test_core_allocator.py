"""Tests for the faithful allocator (Result 1 of the paper).

Validates, empirically, every property of Result 1:
  1. references are plain block indices (pointers)      — by construction
  2. O(1) worst-case time per operation                 — step-count bound
  3. at most m - Theta(p^2) live blocks                 — capacity test
  4. Theta(p^2) extra space for metadata                — space test
  5. single-word read/write/CAS (LL/SC via DISC'20)     — by construction
plus linearizability, wait-freedom under crashes, and robustness to
user writes into live blocks.
"""

import random

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    SimContext, WaitFreeAllocator, Scheduler, closed_loop,
    check_alloc_history, PoolExhausted,
)

POLICIES = ("random", "bursty", "round_robin", "stall_one")


def run_workload(p, policy="random", seed=0, n_ops=150, phased_bursts=False,
                 crash_at=None, **alloc_kw):
    ctx = SimContext(p, seed=seed)
    alloc = WaitFreeAllocator(ctx, shared_batches=4 * p, **alloc_kw)
    sched = Scheduler(seed=seed)
    for pid in range(p):
        if phased_bursts:
            sched.add(pid, _phased(pid, alloc, random.Random(seed * 97 + pid)))
        else:
            sched.add(pid, closed_loop(pid, alloc, n_ops,
                                       random.Random(seed * 97 + pid)))
    sched.run(policy, crash_at=crash_at)
    return ctx, alloc, sched


def _phased(pid, alloc, rng, phases=4):
    held = []
    burst = alloc.ell * 3
    for ph in range(phases):
        if ph % 2 == 0:
            for _ in range(burst):
                b = yield from alloc.allocate(pid)
                for w in range(alloc.mem.k):
                    alloc.mem.words[b][w] = 0xDEADBEEF  # user scribble
                held.append(b)
        else:
            rng.shuffle(held)
            while held:
                yield from alloc.free(pid, held.pop())
    while held:
        yield from alloc.free(pid, held.pop())


@pytest.mark.parametrize("p", [2, 3, 4, 8])
@pytest.mark.parametrize("policy", POLICIES)
def test_safety_under_schedules(p, policy):
    ctx, alloc, _ = run_workload(p, policy, seed=11, phased_bursts=True)
    alloc.check_num_batches_invariant()
    assert ctx.violations == []
    assert check_alloc_history(ctx.history) == []


@pytest.mark.parametrize("p", [2, 4, 8, 16])
def test_constant_time_bound(p):
    """Result 1.2: worst-case steps per op is a constant independent of p."""
    worst = 0
    for policy in POLICIES:
        ctx, alloc, _ = run_workload(p, policy, seed=5, phased_bursts=True)
        assert ctx.violations == []
        worst = max(worst, max(op.steps for op in ctx.history if op.completed))
    # DEAMORT_C(48) + private-op drain + op logic; see allocator.py.
    assert worst <= 70, f"p={p}: worst op took {worst} steps"


def test_step_bound_independent_of_p():
    results = {}
    for p in (2, 16):
        worst = 0
        for policy in POLICIES:
            ctx, _, _ = run_workload(p, policy, seed=5, phased_bursts=True)
            worst = max(worst, max(op.steps for op in ctx.history if op.completed))
        results[p] = worst
    assert results[16] <= results[2] + 12, results


@pytest.mark.parametrize("p", [2, 4, 8])
def test_delayed_ops_complete_within_p_user_ops(p):
    ctx, alloc, _ = run_workload(p, "random", seed=3, phased_bursts=True)
    assert alloc.delayed_started == alloc.delayed_completed + (
        sum(1 for pool in alloc.pools if pool.delayed is not None))
    assert alloc.max_delayed_slices <= p, (
        f"a shared op needed {alloc.max_delayed_slices} > p={p} user ops")


@pytest.mark.parametrize("p", [2, 4, 8])
def test_live_capacity(p):
    """Result 1.3: at least m - Theta(p^2) blocks can be live at once."""
    ctx = SimContext(p, seed=0)
    alloc = WaitFreeAllocator(ctx, shared_batches=6 * p, allow_os_growth=False)
    m = alloc.mem.m
    sched = Scheduler(seed=0)
    got = []

    def greedy(pid):
        try:
            while True:
                b = yield from alloc.allocate(pid)
                got.append(b)
        except PoolExhausted:
            return

    # one process drains everything it can reach
    sched.add(0, greedy(0))
    try:
        sched.run("round_robin")
    except PoolExhausted:
        pass
    # Unreachable: other processes' private pools (<= 2.5*ell each) plus
    # our own residual metadata-held blocks — all Theta(p^2) with ell=4p.
    live = len(got)
    assert live >= m - 11 * p * p - 8 * p, (
        f"p={p}: only {live} of {m} blocks allocatable")
    assert len(set(got)) == live  # all distinct


@pytest.mark.parametrize("p", [2, 4, 8, 16, 32])
def test_space_overhead_quadratic(p):
    """Result 1.4: internal metadata is Theta(p^2) words."""
    ctx = SimContext(p, seed=0)
    alloc = WaitFreeAllocator(ctx, shared_batches=4 * p)
    words = alloc.metadata_words()
    # LLSC (p^2) + psim pool (2(p+1)(2p+1)) + announces/toggles + locals
    assert words <= 12 * p * p + 40 * p + 60, f"p={p}: {words} words"
    assert words >= p * p  # genuinely quadratic components present


def test_crash_wait_freedom():
    """Crashed processes cannot block others (wait-freedom)."""
    p = 6
    ctx = SimContext(p, seed=9)
    alloc = WaitFreeAllocator(ctx, shared_batches=4 * p)
    sched = Scheduler(seed=9)
    for pid in range(p):
        sched.add(pid, _phased(pid, alloc, random.Random(pid)))
    # crash half the processes at staggered points mid-execution
    sched.run("random", crash_at={0: 500, 1: 1500, 2: 2500})
    assert ctx.violations == []
    assert check_alloc_history(ctx.history) == []
    # survivors finished their whole programs
    for pid in (3, 4, 5):
        assert sched.done[pid]
    # and their ops all stayed O(1)
    for op in ctx.history:
        if op.pid in (3, 4, 5) and op.completed:
            assert op.steps <= 70


def test_user_scribble_cannot_corrupt():
    """The allocator never trusts words of live blocks (paper section 1)."""
    ctx, alloc, _ = run_workload(4, "bursty", seed=21, phased_bursts=True)
    assert ctx.violations == []
    assert check_alloc_history(ctx.history) == []


def test_os_growth_when_exhausted():
    p = 2
    ctx = SimContext(p, seed=0)
    alloc = WaitFreeAllocator(ctx, shared_batches=1, allow_os_growth=True)
    sched = Scheduler(seed=0)
    n_target = alloc.mem.m + 3 * alloc.ell   # force growth

    def greedy(pid, n):
        for _ in range(n):
            yield from alloc.allocate(pid)

    sched.add(0, greedy(0, n_target // 2))
    sched.add(1, greedy(1, n_target // 2))
    sched.run("random")
    assert alloc.os_requests > 0
    assert ctx.violations == []


@settings(max_examples=15, deadline=None)
@given(
    p=st.integers(min_value=2, max_value=6),
    seed=st.integers(min_value=0, max_value=2**16),
    max_held=st.integers(min_value=1, max_value=48),
)
def test_property_random_schedules(p, seed, max_held):
    """Hypothesis: no schedule/workload mix violates safety or O(1)."""
    ctx = SimContext(p, seed=seed)
    alloc = WaitFreeAllocator(ctx, shared_batches=4 * p)
    sched = Scheduler(seed=seed)
    for pid in range(p):
        sched.add(pid, closed_loop(pid, alloc, 120,
                                   random.Random(seed + pid), max_held=max_held))
    sched.run("random")
    alloc.check_num_batches_invariant()
    assert ctx.violations == []
    assert check_alloc_history(ctx.history) == []
    assert max(op.steps for op in ctx.history if op.completed) <= 70
