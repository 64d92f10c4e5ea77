"""Speculative decode on shared prefixes + SLO-aware chunk sizing
(DESIGN.md §10): token identity, key-stream determinism, whole-page
rollback accounting, the speculative-episode checker (and its
self-tests), and the torn-rebalance draft-rejection storm.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import models
from repro.configs import get_config, smoke_config
from repro.core import hier_pool, kv_cache
from repro.core.linearizability import check_speculative_history
from repro.core.sim import OpRecord
from repro.serving.engine import Request, ServingEngine, step_collectives
from repro.serving.sched import SchedConfig


@pytest.fixture(scope="module")
def engine_setup():
    cfg = smoke_config(get_config("olmo-1b"))
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _greedy_reference(cfg, params, prompts, max_new=6, dp=1, b_local=2):
    eng = ServingEngine(cfg, params, dp=dp, b_local=b_local, max_len=64)
    reqs = [Request(i, prompt=list(p), max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run(max_steps=500)
    assert all(r.done for r in reqs)
    return [r.out_tokens for r in reqs]


# ================================================= 1. engine identity

class TestSpeculativeIdentity:
    def test_greedy_token_identity_and_invariants(self, engine_setup):
        """Hot-prefix repeat traffic with speculation on: outputs are
        bit-identical to the non-speculative run of the same trace,
        drafts are actually accepted (the feature fired), and after
        EVERY verify/rollback step each shard conserves pages
        (free + live == pages_local) and keeps §4.2's never-dry
        min(private_top) >= ell."""
        cfg, params = engine_setup
        rng = np.random.RandomState(0)
        hot = list(rng.randint(1, 255, 16))          # 2 pages of 8
        prompts = [list(hot) for _ in range(6)] + \
                  [list(rng.randint(1, 255, 10)) for _ in range(3)]
        ref = _greedy_reference(cfg, params, prompts, dp=2)

        eng = ServingEngine(cfg, params, dp=2, b_local=2, max_len=64,
                            speculate=True, draft_len=4)
        ell = hier_pool.lane_ell(eng.state.pool.classes[0])
        reqs = [Request(i, prompt=list(p), max_new_tokens=6)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        for _ in range(400):
            if eng.idle():
                break
            eng.step()
            kv = eng.state.pool.classes[0]
            free_s = np.asarray(hier_pool.free_per_shard(kv))
            live_s = np.asarray(hier_pool.live_per_shard(kv))
            assert np.all(free_s + live_s == eng.pages_local), (
                f"per-shard conservation broken after a step "
                f"(free={free_s.tolist()} live={live_s.tolist()})")
            tops = np.asarray(kv.private_top)
            assert tops.min() >= ell, (
                f"a lane ran dry after a verify/rollback step "
                f"(min={tops.min()}, ell={ell}) — §4.2 violated")
        assert all(r.done for r in reqs)
        assert [r.out_tokens for r in reqs] == ref, \
            "speculation changed greedy output"
        assert eng.stats["spec_accepted"] > 0, "no draft ever accepted"
        assert eng.page_occupancy() == 0.0

    def test_rejected_drafts_roll_back_pages(self, engine_setup):
        """A continuation that matches one real token then diverges:
        every draft is rejected, the whole-page over-allocation is
        rolled back (counted), and output still equals the
        non-speculative stream."""
        cfg, params = engine_setup
        rng = np.random.RandomState(1)
        # 23-token prompt: decode sits mid-page, so a rejected 4-draft
        # lane over-allocates a page that must come back
        prompt = list(rng.randint(1, 255, 23))
        ref = _greedy_reference(cfg, params, [prompt], dp=1, b_local=1)[0]

        eng = ServingEngine(cfg, params, dp=1, b_local=1, max_len=64,
                            speculate=True, draft_len=4)
        key = eng.spec_store.key_of(prompt)
        tail = tuple(prompt[len(key):])
        garbage = tuple((t + 101) % (cfg.vocab - 2) + 1 for t in ref)
        eng.spec_store.record(key, tail + (ref[0],) + garbage)
        r = Request(0, prompt=list(prompt), max_new_tokens=6)
        eng.submit(r)
        eng.run(max_steps=300)
        assert r.done and r.out_tokens == ref
        assert eng.stats["spec_drafted"] > 0
        assert eng.stats["spec_accepted"] == 0
        assert eng.stats["spec_pages_rolled_back"] > 0
        assert eng.stats["accept_hist"].get(0, 0) >= 1
        assert eng.page_occupancy() == 0.0


# ====================================== 2. sampled-key determinism

class TestSampledSpecDeterminism:
    """The fold_in(seed, out_count) key stream must be exactly the
    one-token-at-a-time stream: position i of a draft lane draws key
    out_count + i, acceptance consumes keys in order, rollback never
    skips one."""

    def _sampled_run(self, cfg, params, prompt, seed=7, speculate=False,
                     cont=None):
        eng = ServingEngine(cfg, params, dp=1, b_local=1, max_len=64,
                            speculate=speculate, draft_len=4)
        if cont is not None:
            key = eng.spec_store.key_of(prompt)
            eng.spec_store.record(key, tuple(prompt[len(key):]) + cont)
        r = Request(0, prompt=list(prompt), max_new_tokens=6,
                    temperature=0.9, top_k=12, seed=seed)
        eng.submit(r)
        eng.run(max_steps=300)
        assert r.done
        return r.out_tokens, eng

    def test_all_rejected_drafts_bit_identical(self, engine_setup):
        cfg, params = engine_setup
        rng = np.random.RandomState(3)
        prompt = list(rng.randint(1, 255, 16))
        ref, _ = self._sampled_run(cfg, params, prompt)
        # drafts: the real first sampled token (so the lane fires) then
        # off-vocab-shifted garbage -> every draft rejected
        garbage = tuple((t + 77) % (cfg.vocab - 2) + 1 for t in ref[1:])
        out, eng = self._sampled_run(cfg, params, prompt, speculate=True,
                                     cont=(ref[0],) + garbage)
        assert eng.stats["spec_drafted"] > 0
        assert eng.stats["spec_accepted"] == 0
        assert out == ref, ("all-rejected speculative sampling must be "
                            "bit-identical to non-speculative decode")

    def test_partial_accept_never_skips_keys(self, engine_setup):
        """Continuation = the true sampled stream's first 3 tokens, then
        garbage: the lane accepts a partial prefix, and the resumed key
        indices continue exactly where the accepted stream stopped —
        the full output still equals the non-speculative stream."""
        cfg, params = engine_setup
        rng = np.random.RandomState(4)
        prompt = list(rng.randint(1, 255, 16))
        ref, _ = self._sampled_run(cfg, params, prompt)
        cont = tuple(ref[:3]) + ((ref[3] + 55) % (cfg.vocab - 2) + 1,
                                 (ref[4] + 55) % (cfg.vocab - 2) + 1)
        out, eng = self._sampled_run(cfg, params, prompt, speculate=True,
                                     cont=cont)
        assert eng.stats["spec_accepted"] > 0, "no partial accept fired"
        assert eng.stats["spec_accepted"] < eng.stats["spec_drafted"]
        assert out == ref, ("partial accept skipped or reused a sampling "
                            "key — keyed stream diverged")

    def test_full_accept_matches_sampled_stream(self, engine_setup):
        """Recording the true sampled continuation makes every draft an
        accept and the output is still the same stream."""
        cfg, params = engine_setup
        rng = np.random.RandomState(5)
        prompt = list(rng.randint(1, 255, 16))
        ref, _ = self._sampled_run(cfg, params, prompt)
        out, eng = self._sampled_run(cfg, params, prompt, speculate=True,
                                     cont=tuple(ref))
        assert eng.stats["spec_accepted"] > 0
        assert out == ref
        assert eng.stats["steps"] > 0


# =========================================== 3. SLO-aware chunk sizing

class TestChunkBuckets:
    def test_prefill_shrinks_when_interactive_waits(self, engine_setup):
        """With buckets configured, batch-class prefill runs full-width
        until interactive work arrives, then shrinks to the smallest
        bucket — and the emitted tokens are identical to the fixed-chunk
        run (lane width is output-invisible)."""
        cfg, params = engine_setup
        rng = np.random.RandomState(6)
        std = [list(rng.randint(1, 255, 28)) for _ in range(2)]
        inter = list(rng.randint(1, 255, 6))

        def run(buckets):
            eng = ServingEngine(cfg, params, dp=1, b_local=2, max_len=64,
                                chunk_size=16,
                                sched=SchedConfig(chunk_buckets=buckets))
            reqs = [Request(i, prompt=list(p), max_new_tokens=4,
                            slo="batch") for i, p in enumerate(std)]
            for r in reqs:
                eng.submit(r)
            eng.step()                       # full-width prefill step
            ri = Request(9, prompt=list(inter), max_new_tokens=4,
                         slo="interactive")
            eng.submit(ri)
            eng.run(max_steps=300)
            assert all(r.done for r in reqs + [ri])
            assert eng.page_occupancy() == 0.0
            return [r.out_tokens for r in reqs + [ri]], eng

        out_fixed, eng_fixed = run(())
        out_adapt, eng_adapt = run((1, 4))
        assert out_adapt == out_fixed, "chunk sizing changed tokens"
        hist = eng_adapt.stats["chunk_hist"]
        assert hist.get(16), "full-width prefill never ran"
        assert hist.get(1) or hist.get(4), (
            f"prefill never shrank for the waiting interactive class "
            f"(lane hist {hist})")
        assert set(eng_fixed.stats["chunk_hist"]) <= {1, 16}

    def test_pick_chunk_policy(self, engine_setup):
        """Unit: no latency pressure -> full chunk; interactive queued
        or decoding over lower-priority prefill -> smallest bucket."""
        cfg, params = engine_setup
        eng = ServingEngine(cfg, params, dp=1, b_local=2, max_len=64,
                            chunk_size=16,
                            sched=SchedConfig(chunk_buckets=(4, 8)))
        sched = eng.scheduler
        assert sched.buckets(16) == (4, 8, 16)
        assert sched.pick_chunk(eng, 16) == 16          # idle queue
        # a queued interactive head + lower-priority prefill -> shrink
        eng.submit(Request(0, prompt=[1] * 24, max_new_tokens=2,
                           slo="batch"))
        eng.step()
        assert eng.pending_tokens, "prefill should still be pending"
        eng.scheduler.queues["interactive"].append(
            Request(1, prompt=[2, 3], max_new_tokens=2,
                    slo="interactive"))
        assert sched.pick_chunk(eng, 16) == 4
        eng.scheduler.queues["interactive"].clear()
        assert sched.pick_chunk(eng, 16) == 16


# ------------------------------------------------- pin-gate regression

def test_pin_waits_for_final_whole_page_chunk(engine_setup):
    """Regression (review finding): the feed-build `_fed` update must
    land AFTER the feed-time pin gate.  A 3-whole-page prompt whose
    final page arrives in the last chunk must pin on the post-status
    path (after the step wrote the page) — pinning at feed build would
    capture a NULL table entry, and the pin could never donate."""
    cfg, params = engine_setup                      # page_size = 8
    rng = np.random.RandomState(8)
    prompt = list(rng.randint(1, 255, 24))          # exactly 3 pages
    eng = ServingEngine(cfg, params, dp=1, b_local=2, max_len=64,
                        chunk_size=8,
                        sched=SchedConfig(pin_pages=8))
    r0 = Request(0, prompt=list(prompt), max_new_tokens=3)
    eng.submit(r0)
    eng.run(max_steps=100)
    assert r0.done
    assert eng.stats["pins_created"] == 1
    pin = next(iter(eng.pins.entries.values()))
    assert pin["pages"] == 3
    row = np.asarray(eng.pin_tables)[pin["shard"], pin["row"]]
    assert (row[:3] >= 0).all(), f"pin row holds NULL pages: {row[:4]}"
    # the pin must actually donate to an identical follow-up
    r1 = Request(1, prompt=list(prompt), max_new_tokens=3)
    eng.submit(r1)
    eng.run(max_steps=100)
    assert r1.done and r1.out_tokens == r0.out_tokens
    assert eng.stats["pin_hit_reqs"] == 1, "pinned prefix never donated"
    eng.flush_pins()
    assert eng.page_occupancy() == 0.0


# ============================================ 4. cache-level rollback

def test_kv_cache_rollback_frees_empty_pages():
    """kv_cache.rollback un-appends a tail: pages left holding no token
    return to the pool (shared pages just drop a reference), the
    partial surviving page stays mapped, and conservation holds."""
    cache = kv_cache.create(num_pages=32, page_size=4, kv_heads=1,
                            head_dim=8, max_seqs=2, max_pages_per_seq=6)
    k = jnp.ones((2, 10, 1, 8))
    v = jnp.ones((2, 10, 1, 8))
    lens = jnp.asarray([10, 7], jnp.int32)
    cache, ok = kv_cache.append_chunk(cache, k, v, lens)
    assert bool(ok.all())
    free0 = int(cache.pool.top)
    # seq0: 10 -> 5 tokens (pages 3 -> 2: one page freed);
    # seq1: 7 -> 7 (no-op)
    cache = kv_cache.rollback(cache, jnp.asarray([5, 0], jnp.int32))
    assert [int(x) for x in cache.seq_lens] == [5, 7]
    assert int(cache.pool.top) == free0 + 1
    assert int(cache.page_tables[0, 2]) == -1, "emptied page still mapped"
    assert int(cache.page_tables[0, 1]) >= 0, "partial page unmapped"
    # the surviving prefix still reads back intact
    kk, vv, valid = kv_cache.gather_kv(cache, 0, 8)
    assert int(valid.sum()) == 5
    # conservation: free + live == num_pages
    live = int(kv_cache.block_pool.num_live(cache.pool))
    assert int(cache.pool.top) + live == 32


# ======================================= 5. episode checker self-tests

def _op(opid, name, pid=0, arg=None, result=None, t0=0, t1=1, meta=None):
    rec = OpRecord(opid=opid, pid=pid, name=name, arg=arg,
                   invoke_step=t0, result=result, response_step=t1)
    rec.meta.update(meta or {})
    return rec


class TestSpeculativeChecker:
    def test_clean_episode_passes(self):
        hist = [
            _op(1, "alloc_n", result=[4, 5, 6],
                meta={"spec": "e0", "shard": 1}),
            _op(2, "spec_rollback", arg=[5, 6], t0=2, t1=3,
                meta={"spec": "e0", "shard": 1, "kept": [4]}),
        ]
        assert check_speculative_history(hist) == []

    def test_full_accept_needs_no_rollback(self):
        hist = [_op(1, "alloc_n", result=[7, 8],
                    meta={"spec": "e1", "shard": 0, "kept": [7, 8]})]
        assert check_speculative_history(hist) == []

    def test_leak_detected(self):
        hist = [
            _op(1, "alloc_n", result=[4, 5, 6],
                meta={"spec": "e0", "shard": 0}),
            _op(2, "spec_rollback", arg=[5], t0=2, t1=3,
                meta={"spec": "e0", "shard": 0, "kept": [4]}),
        ]
        errs = check_speculative_history(hist)
        assert any("leak" in e and "[6]" in e for e in errs), errs

    def test_theft_detected(self):
        # rollback frees a page the episode kept
        hist = [
            _op(1, "alloc_n", result=[4, 5],
                meta={"spec": "e0", "shard": 0}),
            _op(2, "spec_rollback", arg=[4, 5], t0=2, t1=3,
                meta={"spec": "e0", "shard": 0, "kept": [4]}),
        ]
        errs = check_speculative_history(hist)
        assert any("theft" in e and "[4]" in e for e in errs), errs

    def test_foreign_page_theft_detected(self):
        # rollback frees a page granted to ANOTHER lane (never to this
        # episode): episode theft + cross-lane free-while-available
        hist = [
            _op(1, "alloc_n", pid=1, result=[9]),
            _op(2, "alloc_n", result=[4, 5],
                meta={"spec": "e0", "shard": 0}, t0=1, t1=2),
            _op(3, "spec_rollback", arg=[5, 9], t0=3, t1=4,
                meta={"spec": "e0", "shard": 0, "kept": [4]}),
        ]
        errs = check_speculative_history(hist)
        assert any("theft" in e and "9" in e for e in errs), errs

    def test_kept_not_granted_detected(self):
        hist = [
            _op(1, "alloc_n", result=[4],
                meta={"spec": "e0", "shard": 0}),
            _op(2, "spec_rollback", arg=[4], t0=2, t1=3,
                meta={"spec": "e0", "shard": 0, "kept": [12]}),
        ]
        errs = check_speculative_history(hist)
        assert any("never granted" in e for e in errs), errs

    def test_cross_shard_rollback_detected(self):
        hist = [
            _op(1, "alloc_n", result=[4, 5],
                meta={"spec": "e0", "shard": 0}),
            _op(2, "spec_rollback", arg=[5], t0=2, t1=3,
                meta={"spec": "e0", "shard": 1, "kept": [4]}),
        ]
        errs = check_speculative_history(hist)
        assert any("span shards" in e for e in errs), errs
        assert any("cross-shard theft" in e for e in errs), errs


# ====================================== 6. torn-rebalance draft storm

class TestSpecRollbackStorm:
    """Adversarial storm: draft lanes over-allocate, verify, and roll
    back WHILE the rebalancer sits inside its torn drain/refill window —
    the §4.2 worst case the draft-page ownership rules must survive
    (DESIGN.md §10)."""

    def _storm(self, seed, leak_lane=None):
        import random
        from repro.core import Scheduler, SimContext
        L, ell, kmax = 3, 4, 4
        st = {"pool": hier_pool.create(num_blocks=96, num_lanes=L, ell=ell),
              "held": {lane: [] for lane in range(L)}}
        total0 = int(hier_pool.total_free(st["pool"]))
        ctx = SimContext(L + 1, seed=seed)
        sched = Scheduler(seed=seed)
        eid = [0]

        def lane_program(lane):
            rng = random.Random(seed * 17 + lane)
            held = st["held"][lane]
            for _ in range(20):
                yield                                 # scheduling point
                # --- speculative episode: over-allocate a draft lane
                want = rng.randint(1, kmax)
                counts = np.zeros(L, np.int32)
                counts[lane] = want
                ep = f"s{seed}-{eid[0]}"
                eid[0] += 1
                rec = ctx.begin_op(lane, "alloc_n", arg=want)
                rec.meta.update(spec=ep, shard=0)
                rec.invoke_step = sched.steps
                yield
                pool, ids = hier_pool.alloc_n(
                    st["pool"], jnp.asarray(counts), kmax)
                st["pool"] = pool
                got = [int(i) for i in np.asarray(ids)[lane] if i >= 0]
                yield
                ctx.end_op(rec, result=got)
                rec.response_step = sched.steps
                if not got:
                    continue
                # --- verify: accept a prefix, reject the rest; the
                # rollback happens INSIDE whatever rebalance window the
                # scheduler has the rebalancer parked in
                a = rng.randint(0, len(got))
                kept, rejected = got[:a], got[a:]
                if leak_lane == lane and rejected:
                    rejected = rejected[:-1]        # bug injection: leak
                held.extend(kept)
                back = np.full((L, kmax), -1, np.int32)
                back[lane, :len(rejected)] = rejected
                rec = ctx.begin_op(lane, "spec_rollback", arg=rejected)
                rec.meta.update(spec=ep, shard=0, kept=kept)
                rec.invoke_step = sched.steps
                yield
                st["pool"] = hier_pool.free_n(st["pool"],
                                              jnp.asarray(back))
                yield
                ctx.end_op(rec)
                rec.response_step = sched.steps
                # occasionally release committed pages (normal free)
                if held and rng.random() < 0.4:
                    k = rng.randint(1, min(len(held), kmax))
                    rel = held[-k:]
                    ids = np.full((L, kmax), -1, np.int32)
                    ids[lane, :k] = rel
                    rec = ctx.begin_op(lane, "free_n", arg=rel)
                    rec.meta.update(shard=0)
                    rec.invoke_step = sched.steps
                    yield
                    st["pool"] = hier_pool.free_n(st["pool"],
                                                  jnp.asarray(ids))
                    del held[-k:]
                    yield
                    ctx.end_op(rec)
                    rec.response_step = sched.steps

        def rebalancer(pid):
            for _ in range(60):
                yield
                st["pool"] = hier_pool.rebalance_drain(st["pool"])
                yield              # <-- torn window: rollbacks land here
                st["pool"] = hier_pool.rebalance_refill(st["pool"])

        for lane in range(L):
            sched.add(lane, lane_program(lane))
        sched.add(L, rebalancer(L))
        sched.run("bursty")

        errs = check_speculative_history(ctx.history)
        live = sum(len(h) for h in st["held"].values())
        if leak_lane is None:
            assert errs == [], errs
            assert int(hier_pool.total_free(st["pool"])) + live == total0
            assert int(hier_pool.num_live(st["pool"])) == live
        return errs

    def test_storm_rollbacks_in_torn_window_conserve(self):
        for seed in (0, 1, 2):
            self._storm(seed)

    def test_storm_checker_catches_injected_leak(self):
        errs = self._storm(3, leak_lane=1)
        assert any("leak" in e for e in errs), (
            "injected rejected-draft leak went undetected")


# ============================================= 7. mesh: one sync, one
# collective per speculative step (dp=4 — the mesh-8 CI job)

@pytest.mark.skipif(len(jax.devices()) < 4, reason="mesh-8 CI job")
def test_speculative_step_one_sync_one_collective(engine_setup):
    """On the dp=4 shard_map plane a draft+verify+rollback step still
    performs exactly ONE device->host sync (the packed status, now
    carrying up to draft_len+1 tokens per slot) and compiles exactly
    ONE collective (the status all_gather)."""
    cfg, params = engine_setup
    rng = np.random.RandomState(9)
    prompt = list(rng.randint(1, 255, 16))
    ref = _greedy_reference(cfg, params, [prompt], max_new=8,
                            dp=1, b_local=1)[0]

    eng = ServingEngine(cfg, params, dp=4, b_local=2, max_len=64,
                        speculate=True, draft_len=3)
    assert eng.mesh is not None
    key = eng.spec_store.key_of(prompt)
    eng.spec_store.record(key, tuple(prompt[len(key):]) + tuple(ref))
    for i in range(4):
        eng.submit(Request(i, prompt=list(prompt), max_new_tokens=8))
    eng.step()                            # admission + first prefill chunk
    while any(eng.pending_tokens.get(s) for s in eng.active):
        eng.step()

    import repro.serving.engine as engine_mod
    syncs = []
    real_asarray = np.asarray

    class CountingNp:
        def __getattr__(self, name):
            return getattr(np, name)

        @staticmethod
        def asarray(x, *a, **kw):
            if isinstance(x, jax.Array):
                syncs.append(x.shape)
            return real_asarray(x, *a, **kw)

    orig = engine_mod.np
    engine_mod.np = CountingNp()
    try:
        steps0 = eng.stats["steps"]
        for _ in range(2):
            eng.step()
    finally:
        engine_mod.np = orig
    assert eng.stats["steps"] == steps0 + 2
    assert len(syncs) == 2, f"expected 1 sync/step, saw {syncs}"
    # width-4 draft lanes: status is [spec_T + 3 + N_CTR, DP, Bl]
    from repro.serving.telemetry import N_CTR
    assert all(s == (eng._spec_T + 3 + N_CTR, 4, 2) for s in syncs), syncs
    assert eng.stats["spec_lanes"] > 0, "steps were not speculative"

    # exactly one collective in the compiled speculative step
    n_gather, n_other = step_collectives(
        eng.compiled_step_text(eng._spec_T, spec=True))
    assert n_gather == 1, f"expected exactly one all_gather, HLO has {n_gather}"
    assert n_other == 0, "unexpected extra collectives in the step"

    eng.run(max_steps=300)
    assert eng.page_occupancy() == 0.0
