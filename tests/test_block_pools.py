"""Tests for the JAX block pool / hierarchical pool / paged KV cache."""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import block_pool, hier_pool, kv_cache
from repro.core.block_pool import NULL


class TestBlockPool:
    def test_alloc_free_roundtrip(self):
        pool = block_pool.create(16)
        pool, ids = block_pool.alloc(pool, jnp.array([True] * 4 + [False] * 4))
        assert int(pool.top) == 12
        assert np.all(np.asarray(ids[:4]) >= 0)
        assert np.all(np.asarray(ids[4:]) == -1)
        pool = block_pool.free(pool, ids)
        assert int(pool.top) == 16

    def test_exhaustion(self):
        pool = block_pool.create(3)
        pool, ids = block_pool.alloc(pool, jnp.ones(5, bool))
        got = np.asarray(ids)
        assert (got >= 0).sum() == 3 and (got == -1).sum() == 2
        assert int(pool.top) == 0

    def test_batch_ops(self):
        pool = block_pool.create(10)
        pool, batch = block_pool.alloc_batch(pool, 4)
        assert int(pool.top) == 6 and np.all(np.asarray(batch) >= 0)
        pool, batch2 = block_pool.alloc_batch(pool, 8)  # too big
        assert np.all(np.asarray(batch2) == -1) and int(pool.top) == 6
        pool = block_pool.free_batch(pool, batch)
        assert int(pool.top) == 10

    def test_jit_and_no_double_alloc(self):
        alloc_j = jax.jit(block_pool.alloc)
        free_j = jax.jit(block_pool.free)
        pool = block_pool.create(64)
        rng = np.random.RandomState(0)
        live = set()
        for step in range(50):
            mask = jnp.asarray(rng.rand(8) < 0.6)
            pool, ids = alloc_j(pool, mask)
            for i in np.asarray(ids):
                if i >= 0:
                    assert i not in live, "double allocation"
                    live.add(int(i))
            if live and rng.rand() < 0.5:
                drop = [live.pop() for _ in range(min(4, len(live)))]
                drop += [-1] * (8 - len(drop))
                pool = free_j(pool, jnp.asarray(drop, jnp.int32))
        assert int(pool.top) == 64 - len(live)

    def test_alloc_n_basic(self):
        pool = block_pool.create(16)
        pool, ids = block_pool.alloc_n(pool, jnp.asarray([2, 0, 3]), 4)
        got = np.asarray(ids)
        assert got.shape == (3, 4)
        assert (got[0] >= 0).sum() == 2 and (got[1] >= 0).sum() == 0
        assert (got[2] >= 0).sum() == 3
        assert int(pool.top) == 11
        live = got[got >= 0].tolist()
        assert len(set(live)) == 5, "duplicate grant"
        pool = block_pool.free(pool, ids.reshape(-1))
        assert int(pool.top) == 16

    def test_alloc_n_prefix_denial(self):
        """All-or-nothing per slot, in slot order: the first infeasible
        slot denies itself and every later slot (monotone cumulative
        demand), so one probe of the last needed id detects failure."""
        pool = block_pool.create(10)
        pool, ids = block_pool.alloc_n(pool, jnp.asarray([2, 0, 3, 6, 1]), 6)
        got = np.asarray(ids)
        assert (got[0] >= 0).sum() == 2 and (got[2] >= 0).sum() == 3
        assert (got[3] >= 0).sum() == 0, "infeasible slot must get nothing"
        assert (got[4] >= 0).sum() == 0, "slots after a denial get nothing"
        assert int(pool.top) == 5

    def test_alloc_n_matches_sequential_alloc(self):
        """alloc_n(counts) hands out the same blocks as repeated alloc."""
        p1 = p2 = block_pool.create(32)
        counts = jnp.asarray([3, 1, 0, 2])
        p1, ids1 = block_pool.alloc_n(p1, counts, 3)
        seq = []
        for s, c in enumerate(np.asarray(counts)):
            row = []
            for _ in range(int(c)):
                p2, one = block_pool.alloc(
                    p2, jnp.asarray([True]))
                row.append(int(one[0]))
            seq.append(row)
        assert int(p1.top) == int(p2.top)
        for s, row in enumerate(seq):
            assert np.asarray(ids1)[s, :len(row)].tolist() == row

    @settings(max_examples=20, deadline=None)
    @given(m=st.integers(8, 64), seed=st.integers(0, 999))
    def test_property_alloc_n_conservation(self, m, seed):
        rng = np.random.RandomState(seed)
        pool = block_pool.create(m)
        live = []
        for _ in range(12):
            if rng.rand() < 0.6:
                counts = jnp.asarray(rng.randint(0, 4, 5))
                pool, ids = block_pool.alloc_n(pool, counts, 3)
                live += [int(i) for i in np.asarray(ids).ravel() if i >= 0]
            elif live:
                k = rng.randint(1, len(live) + 1)
                back = [live.pop() for _ in range(k)]
                back += [-1] * ((-len(back)) % 6)
                pool = block_pool.free(pool, jnp.asarray(back, jnp.int32))
            assert int(pool.top) + len(live) == m
            assert len(set(live)) == len(live)

    @settings(max_examples=20, deadline=None)
    @given(m=st.integers(4, 64), seed=st.integers(0, 999))
    def test_property_conservation(self, m, seed):
        rng = np.random.RandomState(seed)
        pool = block_pool.create(m)
        live = []
        for _ in range(20):
            if rng.rand() < 0.5:
                pool, ids = block_pool.alloc(pool, jnp.asarray(rng.rand(6) < 0.7))
                live += [int(i) for i in np.asarray(ids) if i >= 0]
            elif live:
                k = rng.randint(1, len(live) + 1)
                back = [live.pop() for _ in range(k)] + [-1] * (6 - k)
                pool = block_pool.free(pool, jnp.asarray(back[:6], jnp.int32))
                live += [b for b in back[6:] if b >= 0]
            assert int(pool.top) + len(live) == m
            assert len(set(live)) == len(live)


class TestHierPool:
    def test_private_only_common_case(self):
        pool = hier_pool.create(num_blocks=256, num_lanes=4, ell=8)
        shared_top0 = int(pool.shared.top)
        # a few allocs per lane: shared pool untouched
        for _ in range(3):
            pool, ids = hier_pool.alloc(pool, jnp.ones(4, bool))
            assert np.all(np.asarray(ids) >= 0)
        assert int(pool.shared.top) == shared_top0

    def test_rebalance_refills_and_drains(self):
        pool = hier_pool.create(num_blocks=256, num_lanes=2, ell=8)
        # drain lane 0 below ell
        for _ in range(7):
            pool, _ = hier_pool.alloc(pool, jnp.asarray([True, False]))
        assert int(pool.private_top[0]) == 1
        pool = hier_pool.rebalance(pool)
        assert int(pool.private_top[0]) == 9   # refilled one batch
        # now free many into lane 1 to exceed 2*ell
        ids = []
        for _ in range(20):
            pool, got = hier_pool.alloc(pool, jnp.asarray([True, True]))
            ids.append(np.asarray(got))
        for got in ids:
            pool = hier_pool.free(pool, jnp.asarray([NULL, got[0]], jnp.int32))
            pool = hier_pool.free(pool, jnp.asarray([NULL, got[1]], jnp.int32))
        assert int(pool.private_top[1]) > 16
        before = int(pool.shared.top)
        total_before = int(hier_pool.total_free(pool))
        pool = hier_pool.rebalance(pool)
        # lane 1 drained one batch (+8 shared); lane 0 (empty after the
        # alloc storm) refilled one batch (-8 shared): net zero, but both
        # lanes are back inside [ell, 2*ell] and blocks are conserved.
        assert int(pool.private_top[1]) <= 2 * 8
        assert int(pool.private_top[0]) == 8
        assert int(pool.shared.top) == before
        assert int(hier_pool.total_free(pool)) == total_before

    def test_alloc_n_private_only(self):
        """Per-lane batched demand is served from private stacks alone."""
        pool = hier_pool.create(num_blocks=256, num_lanes=4, ell=8)
        shared_top0 = int(pool.shared.top)
        pool, ids = hier_pool.alloc_n(pool, jnp.asarray([3, 8, 0, 5]), 8)
        got = np.asarray(ids)
        assert [(r >= 0).sum() for r in got] == [3, 8, 0, 5]
        assert int(pool.shared.top) == shared_top0
        # a lane demanding more than its private stack is denied whole
        pool, ids = hier_pool.alloc_n(pool, jnp.asarray([0, 1, 0, 0]), 8)
        assert not (np.asarray(ids) >= 0).any()

    def test_adversarial_full_batch_drain_never_dry(self):
        """§4.2 invariant: lanes draining a FULL batch (ell blocks) every
        step, with one rebalance per step, never observe a dry private
        pool — the private stack always covers the next step's worst-case
        demand because refill restores >= ell blocks whenever the stack
        drops below ell."""
        L, ell, steps, hold = 4, 8, 60, 3
        pool = hier_pool.create(num_blocks=L * ell * (hold + 4),
                                num_lanes=L, ell=ell)
        total0 = int(hier_pool.total_free(pool))
        alloc_j = jax.jit(hier_pool.alloc_n, static_argnums=(2,))
        free_j = jax.jit(hier_pool.free)
        reb = jax.jit(hier_pool.rebalance)
        held = []          # FIFO of [L, ell] batches, freed after `hold`
        live = 0
        for step in range(steps):
            pool, ids = alloc_j(pool, jnp.full((L,), ell, jnp.int32), ell)
            got = np.asarray(ids)
            assert (got >= 0).all(), (
                f"step {step}: a lane ran dry (paper §4.2 violated)")
            held.append(got)
            live += L * ell
            if len(held) > hold:
                batch = held.pop(0)
                for k in range(ell):      # frees trickle back per lane
                    pool = free_j(pool, jnp.asarray(batch[:, k]))
                live -= L * ell
            pool = reb(pool)
            assert int(hier_pool.total_free(pool)) + live == total0, (
                f"step {step}: blocks lost or duplicated")
        # drain everything back and re-check conservation
        while held:
            batch = held.pop(0)
            for k in range(ell):
                pool = free_j(pool, jnp.asarray(batch[:, k]))
        pool = reb(pool)
        assert int(hier_pool.total_free(pool)) == total0

    def test_rebalance_conserves_under_random_storms(self):
        """No block is lost or duplicated across many rebalances under
        randomized alloc/free storms (conservation + uniqueness)."""
        rng = np.random.RandomState(7)
        L, ell = 6, 4
        pool = hier_pool.create(num_blocks=512, num_lanes=L, ell=ell)
        total0 = int(hier_pool.total_free(pool))
        live = set()
        for step in range(40):
            counts = jnp.asarray(rng.randint(0, ell + 1, L))
            pool, ids = hier_pool.alloc_n(pool, counts, ell)
            for i in np.asarray(ids).ravel():
                if i >= 0:
                    assert i not in live, "duplicate allocation"
                    live.add(int(i))
            if live and rng.rand() < 0.7:
                back = np.full(L, -1, np.int32)
                for lane in range(min(L, len(live))):
                    back[lane] = live.pop()
                pool = hier_pool.free(pool, jnp.asarray(back))
            pool = hier_pool.rebalance(pool)
            assert int(hier_pool.total_free(pool)) + len(live) == total0

    def test_conservation_under_jit(self):
        step_alloc = jax.jit(hier_pool.alloc)
        step_free = jax.jit(hier_pool.free)
        reb = jax.jit(hier_pool.rebalance)
        pool = hier_pool.create(num_blocks=512, num_lanes=8, ell=8)
        total = int(hier_pool.total_free(pool))
        rng = np.random.RandomState(1)
        live = []
        for step in range(60):
            pool, ids = step_alloc(pool, jnp.asarray(rng.rand(8) < 0.7))
            live += [int(i) for i in np.asarray(ids) if i >= 0]
            if live and rng.rand() < 0.5:
                back = np.full(8, -1, np.int32)
                for lane in range(min(4, len(live))):
                    back[lane] = live.pop()
                pool = step_free(pool, jnp.asarray(back))
                live += [int(b) for b in back if b >= 0 and False]
            if step % 4 == 0:
                pool = reb(pool)
            assert int(hier_pool.total_free(pool)) + len(live) == total
            assert len(set(live)) == len(live)


class TestHierPoolFreeN:
    def test_free_n_returns_to_lane_with_spill(self):
        pool = hier_pool.create(num_blocks=128, num_lanes=2, ell=2)  # cap 6
        total0 = int(hier_pool.total_free(pool))
        # grab 10 blocks for lane 0 via bulk (lane holds only 2)
        pool, ids = hier_pool.alloc_from_shared(
            pool, jnp.asarray([10, 0]), 10)
        assert (np.asarray(ids)[0] >= 0).all()
        top_before = int(pool.private_top[0])
        shared_before = int(pool.shared.top)
        pool = hier_pool.free_n(pool, ids)
        # lane takes what fits (cap 6), the rest spills to shared
        assert int(pool.private_top[0]) == 6
        assert int(pool.shared.top) == shared_before + 10 - (6 - top_before)
        assert int(hier_pool.total_free(pool)) == total0
        assert int(hier_pool.num_live(pool)) == 0

    def test_free_n_shared_page_released_once(self):
        """Two lanes releasing a shared block in ONE call: refcount 2
        drops to 0 and the block returns to exactly one stack."""
        pool = hier_pool.create(num_blocks=64, num_lanes=2, ell=2)
        total0 = int(hier_pool.total_free(pool))
        pool, got = hier_pool.alloc(pool, jnp.asarray([True, False]))
        b = int(got[0])
        pool = hier_pool.addref(pool, got)             # second reference
        assert int(hier_pool.total_free(pool)) == total0 - 1
        pool = hier_pool.free_n(pool, jnp.asarray([[b], [b]], jnp.int32))
        assert int(hier_pool.total_free(pool)) == total0
        assert int(hier_pool.num_live(pool)) == 0
        # and a partial release keeps the block off every stack
        pool, got = hier_pool.alloc(pool, jnp.asarray([True, False]))
        b = int(got[0])
        pool = hier_pool.addref(pool, got)
        pool = hier_pool.free_n(pool, jnp.asarray([[b], [NULL]], jnp.int32))
        assert int(hier_pool.total_free(pool)) == total0 - 1
        assert int(pool.shared.refcount[b]) == 1
        pool = hier_pool.free_n(pool, jnp.asarray([[b], [NULL]], jnp.int32))
        assert int(hier_pool.total_free(pool)) == total0

    def test_create_vectorized_matches_sequential_carve(self):
        """The one-shot warm-up hands lane i exactly the batch the old
        per-lane alloc_batch loop would have."""
        pool = hier_pool.create(num_blocks=32, num_lanes=3, ell=4)
        ref = block_pool.create(32)
        for lane in range(3):
            ref, batch = block_pool.alloc_batch(ref, 4)
            assert np.asarray(pool.private_ids)[lane, :4].tolist() == \
                np.asarray(batch).tolist()
        assert int(pool.shared.top) == int(ref.top)

    def test_dp_wrappers_shard_local(self):
        pool = hier_pool.create_dp(2, 64, 4, 2)
        pool, ids = hier_pool.alloc_n_dp(pool, jnp.full((2, 4), 2), 2)
        got = np.asarray(ids)
        assert (got >= 0).all()
        # shards carve identical (shard-local) id spaces independently
        assert np.array_equal(got[0], got[1])
        pool = hier_pool.free_n_dp(pool, ids)
        pool = hier_pool.rebalance_dp(pool)
        assert int(hier_pool.total_free(pool)) == 128
        assert np.asarray(pool.private_top).min() >= 2


class TestBatchHistoriesLinearize:
    """Satellite: adversarial scheduler runs over the device pool's
    batch ops, checked with the expanded-history linearizability test;
    a crash between the two rebalance phases must conserve blocks."""

    def _storm(self, seed, crash_rebalancer_at=None, crash_lane=None):
        import random
        from repro.core import (Scheduler, SimContext,
                                check_batch_alloc_history)
        L, ell, kmax = 3, 4, 4
        st = {"pool": hier_pool.create(num_blocks=96, num_lanes=L, ell=ell),
              "held": {lane: [] for lane in range(L)}}
        total0 = int(hier_pool.total_free(st["pool"]))
        ctx = SimContext(L + 1, seed=seed)
        sched = Scheduler(seed=seed)

        def lane_program(lane):
            # intervals use the scheduler's step clock and straddle a
            # yield, so ops genuinely overlap across lanes; the pool op
            # itself is one atomic point inside the interval
            rng = random.Random(seed * 31 + lane)
            held = st["held"][lane]
            for _ in range(25):
                yield                                     # scheduling point
                if not held or rng.random() < 0.55:
                    want = rng.randint(1, kmax)
                    counts = np.zeros(L, np.int32)
                    counts[lane] = want
                    rec = ctx.begin_op(lane, "alloc_n", arg=want)
                    rec.invoke_step = sched.steps
                    yield
                    pool, ids = hier_pool.alloc_n(
                        st["pool"], jnp.asarray(counts), kmax)
                    st["pool"] = pool
                    got = [int(i) for i in np.asarray(ids)[lane] if i >= 0]
                    held.extend(got)
                    yield
                    ctx.end_op(rec, result=got)
                    rec.response_step = sched.steps
                else:
                    k = rng.randint(1, min(len(held), kmax))
                    back = held[-k:]                # peek — pop only at the
                    ids = np.full((L, kmax), -1, np.int32)   # linearization
                    ids[lane, :k] = back            # point below, atomically
                    rec = ctx.begin_op(lane, "free_n", arg=back)
                    rec.invoke_step = sched.steps
                    yield
                    st["pool"] = hier_pool.free_n(st["pool"],
                                                  jnp.asarray(ids))
                    del held[-k:]                   # atomic with the op: a
                    yield                           # crash on either yield
                    ctx.end_op(rec)                 # leaves ledger == pool
                    rec.response_step = sched.steps

        def rebalancer(pid):
            for _ in range(40):
                yield
                st["pool"] = hier_pool.rebalance_drain(st["pool"])
                yield                      # <-- crash window: torn rebalance
                st["pool"] = hier_pool.rebalance_refill(st["pool"])

        for lane in range(L):
            sched.add(lane, lane_program(lane))
        sched.add(L, rebalancer(L))
        crash_at = {}
        if crash_rebalancer_at is not None:
            crash_at[L] = crash_rebalancer_at
        if crash_lane is not None:
            crash_at[crash_lane] = crash_rebalancer_at or 40
        sched.run("bursty", crash_at=crash_at)

        errs = check_batch_alloc_history(ctx.history)
        assert errs == [], errs
        live = sum(len(h) for h in st["held"].values())
        assert int(hier_pool.total_free(st["pool"])) + live == total0, (
            "blocks lost or duplicated (crashed holders counted live)")
        assert int(hier_pool.num_live(st["pool"])) == live

    def test_adversarial_batch_histories(self):
        for seed in (0, 1, 2):
            self._storm(seed)

    def test_crash_mid_rebalance_conserves(self):
        """The rebalancer dies between drain and refill: the drained
        batch sits in the shared pool, nothing is lost, lanes keep
        operating on their private stacks."""
        self._storm(seed=5, crash_rebalancer_at=37)

    def test_crash_lane_holding_blocks_conserves(self):
        """A user lane crashes while holding live blocks: they stay
        allocated (refcount 1) and conservation accounts for them."""
        self._storm(seed=7, crash_rebalancer_at=None, crash_lane=1)


class TestPagedKVCache:
    def _mk(self, **kw):
        d = dict(num_pages=32, page_size=4, kv_heads=2, head_dim=8,
                 max_seqs=3, max_pages_per_seq=8, dtype=jnp.float32)
        d.update(kw)
        return kv_cache.create(**d)

    def test_append_and_gather(self):
        cache = self._mk()
        T = 10
        ks = np.random.RandomState(0).randn(T, 3, 2, 8).astype(np.float32)
        vs = np.random.RandomState(1).randn(T, 3, 2, 8).astype(np.float32)
        for t in range(T):
            cache, ok = kv_cache.append(
                cache, jnp.asarray(ks[t]), jnp.asarray(vs[t]),
                jnp.ones(3, bool))
            assert bool(jnp.all(ok))
        assert np.all(np.asarray(cache.seq_lens) == T)
        for s in range(3):
            k, v, valid = kv_cache.gather_kv(cache, s, max_len=12)
            np.testing.assert_allclose(np.asarray(k)[:T], ks[:, s], rtol=1e-6)
            np.testing.assert_allclose(np.asarray(v)[:T], vs[:, s], rtol=1e-6)
            assert int(valid.sum()) == T

    def test_release_returns_pages(self):
        cache = self._mk()
        for t in range(8):
            cache, _ = kv_cache.append(
                cache, jnp.zeros((3, 2, 8)), jnp.zeros((3, 2, 8)),
                jnp.ones(3, bool))
        used = 32 - int(cache.pool.top)
        assert used == 3 * 2   # 8 tokens = 2 pages of 4, per seq
        cache = kv_cache.release(cache, jnp.asarray([True, False, True]))
        assert int(cache.pool.top) == 32 - 2
        assert int(cache.seq_lens[1]) == 8

    def test_page_exhaustion_reports_not_corrupts(self):
        cache = self._mk(num_pages=2, max_seqs=2, max_pages_per_seq=4)
        oks = []
        for t in range(6):
            cache, ok = kv_cache.append(
                cache, jnp.zeros((2, 2, 8)), jnp.zeros((2, 2, 8)),
                jnp.ones(2, bool))
            oks.append(np.asarray(ok))
        # 2 pages serve 1 page per seq (4 tokens); the 5th token needs a
        # second page and must fail cleanly for both seqs
        assert oks[3].all() and not oks[4].any()
        assert np.all(np.asarray(cache.seq_lens) == 4)

    def test_append_under_jit(self):
        cache = self._mk()
        app = jax.jit(kv_cache.append)
        for t in range(5):
            cache, ok = app(cache, jnp.ones((3, 2, 8)), jnp.ones((3, 2, 8)),
                            jnp.ones(3, bool))
        assert np.all(np.asarray(cache.seq_lens) == 5)

    def test_gather_kv_partial_page_not_truncated(self):
        """Regression: max_len not a multiple of page_size must include
        the trailing partial page (was silently dropped by floor div)."""
        cache = self._mk()          # psz=4
        T = 10
        ks = np.random.RandomState(2).randn(T, 3, 2, 8).astype(np.float32)
        for t in range(T):
            cache, _ = kv_cache.append(
                cache, jnp.asarray(ks[t]), jnp.asarray(ks[t]),
                jnp.ones(3, bool))
        k, _, valid = kv_cache.gather_kv(cache, 0, max_len=10)
        assert int(valid.sum()) == 10, "partial page tokens were dropped"
        np.testing.assert_allclose(
            np.asarray(k)[np.asarray(valid)], ks[:, 0], rtol=1e-6)
        # max_len below seq_len still trims to max_len
        _, _, valid7 = kv_cache.gather_kv(cache, 0, max_len=7)
        assert int(valid7.sum()) == 7

    def test_append_chunk_matches_sequential(self):
        """append_chunk(C tokens) == C x append, including ragged lens."""
        c1 = c2 = self._mk()
        rng = np.random.RandomState(3)
        ks = rng.randn(3, 7, 2, 8).astype(np.float32)
        vs = rng.randn(3, 7, 2, 8).astype(np.float32)
        lens = np.array([7, 5, 0], np.int32)
        c1, ok = kv_cache.append_chunk(
            c1, jnp.asarray(ks), jnp.asarray(vs), jnp.asarray(lens))
        assert np.asarray(ok).all()
        for t in range(7):
            c2, _ = kv_cache.append(
                c2, jnp.asarray(ks[:, t]), jnp.asarray(vs[:, t]),
                jnp.asarray(t < lens))
        assert np.array_equal(np.asarray(c1.seq_lens), np.asarray(c2.seq_lens))
        assert int(c1.pool.top) == int(c2.pool.top)
        for s in range(3):
            k1, v1, m1 = kv_cache.gather_kv(c1, s, max_len=8)
            k2, v2, m2 = kv_cache.gather_kv(c2, s, max_len=8)
            assert int(m1.sum()) == int(m2.sum()) == lens[s]
            np.testing.assert_allclose(np.asarray(k1)[np.asarray(m1)],
                                       np.asarray(k2)[np.asarray(m2)])
            np.testing.assert_allclose(np.asarray(v1)[np.asarray(m1)],
                                       np.asarray(v2)[np.asarray(m2)])

    def test_append_chunk_exhaustion_all_or_nothing(self):
        """A chunk that cannot get all its pages appends nothing."""
        cache = self._mk(num_pages=3, max_seqs=2, max_pages_per_seq=4)
        ks = jnp.zeros((2, 8, 2, 8))
        # seq 0 wants 2 pages, seq 1 wants 2 pages; only 3 pages exist
        cache, ok = kv_cache.append_chunk(
            cache, ks, ks, jnp.asarray([8, 8]))
        got = np.asarray(ok)
        assert got[0] and not got[1], "second chunk must fail whole"
        assert np.asarray(cache.seq_lens).tolist() == [8, 0]
        assert int(cache.pool.top) == 1

    def test_append_chunk_table_overflow_fails_clean(self):
        cache = self._mk(max_pages_per_seq=2)      # capacity 8 tokens
        ks = jnp.zeros((3, 6, 2, 8))
        cache, ok = kv_cache.append_chunk(
            cache, ks, ks, jnp.asarray([6, 6, 6]))
        assert np.asarray(ok).all()
        cache, ok = kv_cache.append_chunk(       # 6 more would need page 3
            cache, ks, ks, jnp.asarray([6, 0, 2]))
        got = np.asarray(ok)
        assert not got[0] and got[1] and got[2]
        assert np.asarray(cache.seq_lens).tolist() == [6, 6, 8]

    def test_append_chunk_under_jit_interleaved_with_append(self):
        cache = self._mk()
        appc = jax.jit(kv_cache.append_chunk)
        app = jax.jit(kv_cache.append)
        cache, ok = appc(cache, jnp.ones((3, 6, 2, 8)), jnp.ones((3, 6, 2, 8)),
                         jnp.asarray([6, 3, 1]))
        cache, ok2 = app(cache, jnp.ones((3, 2, 8)), jnp.ones((3, 2, 8)),
                         jnp.ones(3, bool))
        assert np.asarray(ok).all() and np.asarray(ok2).all()
        assert np.asarray(cache.seq_lens).tolist() == [7, 4, 2]
