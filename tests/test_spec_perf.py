"""Making speculation pay (DESIGN.md §12): the page-grouped verify-
attention kernel vs its jnp oracle, the chunked-vocab argmax projection,
the n-gram drafter, and accept-rate-gated drafting — including mid-
request on->off->on gating flips that must stay token-identical for
greedy AND sampled decode with a leak-free speculative history.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import models
from repro.configs import get_config, smoke_config
from repro.core.linearizability import check_speculative_history
from repro.core.sim import OpRecord
from repro.kernels.verify_attention import (build_verify_schedule,
                                            verify_attention_ref)
from repro.kernels.verify_attention.kernel import (
    verify_attention as verify_attention_kernel)
from repro.models.layers import logits_apply, logits_argmax_chunked
from repro.serving.engine import Request, ServingEngine
from repro.serving.prefix_cache import SpeculationStore


@pytest.fixture(scope="module")
def engine_setup():
    cfg = smoke_config(get_config("olmo-1b"))
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# ================================ 1. verify-attention kernel vs oracle

def _spec_tables(rng, B, T, psz, maxp, P, overlap: str):
    """Block tables + base lens shaped like a verify step: B draft
    lanes mid-generation, sharing 0 / some / all of their prefix pages
    (the refcounted sharing `share_prefix_step` produces)."""
    base = rng.randint(psz, (maxp - 1) * psz - T, size=B).astype(np.int32)
    tbl = np.full((B, maxp), -1, np.int32)
    shared = rng.choice(P, size=maxp, replace=False)
    for b in range(B):
        npages = int(np.ceil((int(base[b]) + T) / psz))
        for i in range(npages):
            if overlap == "all" or (overlap == "prefix" and i < 2):
                tbl[b, i] = shared[i]
            else:
                tbl[b, i] = int(rng.randint(0, P))
    return jnp.asarray(tbl), jnp.asarray(base)


class TestVerifyAttentionKernel:
    @pytest.mark.parametrize("B,T,H,KH,hd,psz,maxp,P,overlap", [
        (4, 5, 4, 2, 32, 8, 6, 64, "prefix"),     # draft_len 4
        (8, 3, 4, 2, 32, 8, 6, 64, "all"),        # draft_len 2, hot pages
        (2, 2, 4, 4, 16, 4, 8, 32, "none"),       # draft_len 1, no GQA
        (6, 5, 8, 2, 16, 16, 4, 48, "prefix"),    # big pages, GQA 4
        (3, 4, 4, 1, 32, 8, 6, 32, "all"),        # single kv head
    ])
    def test_vs_ref_sweep(self, B, T, H, KH, hd, psz, maxp, P, overlap):
        rng = np.random.RandomState(hash((B, T, psz, overlap)) % 2 ** 31)
        q = jnp.asarray(rng.randn(B, T, H, hd), jnp.float32)
        kp = jnp.asarray(rng.randn(P, KH, psz, hd), jnp.float32)
        vp = jnp.asarray(rng.randn(P, KH, psz, hd), jnp.float32)
        tbl, base = _spec_tables(rng, B, T, psz, maxp, P, overlap)
        ref = verify_attention_ref(q, kp, vp, tbl, base)
        out = verify_attention_kernel(q, kp, vp, tbl, base, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)

    def test_matches_chunk_attention_path(self, engine_setup):
        """verify_attention is bit-for-bit the math of the non-spec
        chunk path's oracle — the schedule may not change a single
        output element."""
        from repro.kernels.paged_attention.ref import (
            paged_attention_chunk_ref)
        rng = np.random.RandomState(11)
        q = jnp.asarray(rng.randn(3, 4, 4, 16), jnp.float32)
        kp = jnp.asarray(rng.randn(24, 2, 8, 16), jnp.float32)
        vp = jnp.asarray(rng.randn(24, 2, 8, 16), jnp.float32)
        tbl, base = _spec_tables(rng, 3, 4, 8, 5, 24, "prefix")
        a = verify_attention_ref(q, kp, vp, tbl, base)
        b = paged_attention_chunk_ref(q, kp, vp, tbl, base)
        assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_schedule_invariants(self):
        """The sorted schedule covers every resident in-window (lane,
        slot) exactly once, groups equal pages into single contiguous
        runs (the one-DMA-per-hot-page property), and parks dead items
        at the tail."""
        rng = np.random.RandomState(7)
        B, T, psz, maxp, P = 6, 5, 8, 6, 32
        tbl, base = _spec_tables(rng, B, T, psz, maxp, P, "prefix")
        pages, lanes, slots = map(np.asarray,
                                  build_verify_schedule(tbl, base, T, psz))
        assert pages.shape == (B * maxp,)
        live = pages >= 0
        # dead items strictly at the tail
        assert not np.any(live[np.argmax(~live):]) or np.all(live)
        # sorted ascending -> every page id is one contiguous run
        lp = pages[live]
        assert np.all(np.diff(lp) >= 0)
        runs = 1 + int(np.sum(np.diff(lp) != 0))
        assert runs == len(np.unique(lp))
        # exact coverage: each resident in-window table entry once
        tbl_np, base_np = np.asarray(tbl), np.asarray(base)
        want = {(b, i) for b in range(B) for i in range(maxp)
                if tbl_np[b, i] >= 0 and i * psz <= base_np[b] + T - 1}
        got = list(zip(lanes[live].tolist(), slots[live].tolist()))
        assert len(got) == len(set(got)) == len(want)
        assert set(got) == want
        for b, i in want:
            j = got.index((b, i))
            assert pages[live][j] == tbl_np[b, i]

    def test_shared_pages_fewer_runs_than_visits(self):
        """With every lane reading the same pages, the live region
        collapses to one run per unique page: B visits per page, one
        potential DMA."""
        rng = np.random.RandomState(9)
        B, T, psz, maxp, P = 8, 4, 8, 4, 16
        tbl, base = _spec_tables(rng, B, T, psz, maxp, P, "all")
        pages, _, _ = map(np.asarray,
                          build_verify_schedule(tbl, base, T, psz))
        lp = pages[pages >= 0]
        runs = 1 + int(np.sum(np.diff(lp) != 0))
        assert runs == len(np.unique(lp)) < len(lp)


# ======================================= 2. chunked-vocab projection

class TestChunkedArgmax:
    def test_matches_full_projection(self, engine_setup):
        cfg, params = engine_setup
        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(2, 3, 5, cfg.d_model), jnp.float32)
        full = jnp.argmax(logits_apply(cfg, params["embed"], x), axis=-1)
        for chunk in (16, 100, 256, 1024):   # vocab 256: split/odd/exact/1
            got = logits_argmax_chunked(cfg, params["embed"], x, chunk=chunk)
            assert np.array_equal(np.asarray(got), np.asarray(full)), chunk

    def test_tie_break_is_first_max(self):
        cfg = smoke_config(get_config("olmo-1b"))
        d = cfg.d_model
        # lm_head with duplicated columns -> exact logit ties
        w = np.zeros((d, 8), np.float32)
        w[:, 2] = 1.0
        w[:, 5] = 1.0          # same column: tie between ids 2 and 5
        params = {"lm_head": jnp.asarray(w)}
        x = jnp.ones((3, d), jnp.float32)
        got = logits_argmax_chunked(cfg, params, x, chunk=3)
        assert np.all(np.asarray(got) == 2), "chunked argmax broke the " \
            "first-max tie-break jnp.argmax guarantees"


# ============================================== 3. n-gram drafting

class TestNgramDrafter:
    def test_exact_replay_still_wins(self):
        st = SpeculationStore(page_size=4)
        key = (1, 2, 3, 4)
        st.record(key, (10, 11, 12, 13, 14))
        assert st.draft(key, (10, 11), 3) == [12, 13, 14]

    def test_ngram_fallback_extends_beyond_replay(self):
        """A suffix no stream starts with still drafts when its last
        tokens appear mid-stream — the drafter follows the n-gram."""
        st = SpeculationStore(page_size=4, ngram=3)
        key = (1, 2, 3, 4)
        st.record(key, (10, 11, 12, 13, 14, 15))
        # suffix (99, 12, 13) matches no stream prefix, but (12, 13)
        # ... actually (99, 12, 13)[-3:] has no occurrence; g=2 matches
        assert st.draft(key, (99, 12, 13), 2) == [14, 15]

    def test_ngram_prefers_longest_gram(self):
        st = SpeculationStore(page_size=4, ngram=3)
        key = (1, 2, 3, 4)
        st.record(key, (7, 8, 9, 100, 8, 9, 200))
        # g=2 tail (8, 9): rightmost occurrence predicts 200, and the
        # rightmost match wins within a stream
        assert st.draft(key, (50, 8, 9), 1) == [200]

    def test_no_history_no_draft(self):
        st = SpeculationStore(page_size=4)
        assert st.draft((1, 2, 3, 4), (9,), 4) == []

    def test_accept_ewma(self):
        st = SpeculationStore(page_size=4, ewma_alpha=0.5)
        key = (1, 2, 3, 4)
        assert st.accept_rate(key) is None
        st.observe(key, 4, 4)
        assert st.accept_rate(key) == 1.0
        st.observe(key, 4, 0)
        assert st.accept_rate(key) == 0.5
        st.observe(key, 0, 0)            # no drafts -> no update
        assert st.accept_rate(key) == 0.5

    def test_ewma_survives_state_roundtrip(self):
        st = SpeculationStore(page_size=4)
        key = (1, 2, 3, 4)
        st.record(key, (5, 6, 7))
        st.observe(key, 4, 2)
        st2 = SpeculationStore(page_size=4)
        st2.load_state(st.to_state())
        assert st2.accept_rate(key) == st.accept_rate(key)
        assert st2.to_state() == st.to_state()


# ======================================= 4. accept-rate-gated drafting

class _FrozenCosts(dict):
    """Cost model pinned for deterministic gating tests: the engine's
    per-step recorder writes are ignored."""

    def __setitem__(self, k, v):
        pass


def _pin_costs(eng, ratios):
    """Install a frozen measured cost model: width-1 decode costs 1.0,
    width-(k+1) spec steps cost ``ratios[k]``."""
    costs = _FrozenCosts({(1, False): 1.0})
    for k, r in ratios.items():
        dict.__setitem__(costs, (k + 1, True), float(r))
    eng._step_cost = costs


class TestBreakEvenGate:
    def _engine(self, engine_setup, **kw):
        cfg, params = engine_setup
        return ServingEngine(cfg, params, dp=1, b_local=2, max_len=64,
                             speculate=True, draft_len=4, **kw)

    def test_unmeasured_prefix_drafts_full(self, engine_setup):
        eng = self._engine(engine_setup)
        assert eng._gate_k(("k",), 4) == 4

    def test_draft_len_shrinks_before_disabling(self, engine_setup):
        """The gate walks k down as the accept EWMA drops: measured
        costs make k=4 uneconomical before k=1 is."""
        eng = self._engine(engine_setup)
        key = ("k",)
        # fallback linear model (slope 0.25): ratio(k) = 1 + k/4.
        # expected tokens 1 + a + ... + a^k vs ratio:
        #   a=0.9 -> k=4 (4.10 >= 2.00); a=0.5 -> k=3 (1.875 >= 1.75
        #   but 1.9375 < 2.0 at k=4); a=0.2 -> 0 (1.2 < 1.25)
        for a, want in [(0.9, 4), (0.5, 3), (0.2, 0)]:
            eng.spec_store._accept[key] = a
            assert eng._gate_k(key, 4) == want, (a, want)

    def test_measured_costs_override_fallback(self, engine_setup):
        eng = self._engine(engine_setup)
        key = ("k",)
        eng.spec_store._accept[key] = 0.6
        # cheap verify lane (kernel + slimming did their job): k=4
        # costs only 1.3 decode steps -> even a=0.6 clears it
        _pin_costs(eng, {4: 1.3})
        assert eng._gate_k(key, 4) == 4
        # expensive verify lane: a=0.6 yields 2.12 expected tokens < 3
        _pin_costs(eng, {4: 3.0})
        assert eng._gate_k(key, 4) < 4

    def test_gate_off_passes_through(self, engine_setup):
        eng = self._engine(engine_setup, spec_gate=False)
        eng.spec_store._accept[("k",)] = 0.0
        assert eng._gate_k(("k",), 4) == 4


# ================================ 5. mid-request gating flips (on->off->on)

class TestGatingFlipIdentity:
    """A request whose prefix's accept-rate EWMA toggles speculation
    on->off->on must stay token-identical: the fold_in(seed, out_count)
    stream admits no skipped or reused key indices at either flip."""

    def _reference(self, cfg, params, prompt, max_new, sampled):
        eng = ServingEngine(cfg, params, dp=1, b_local=1, max_len=64)
        kw = dict(temperature=0.9, top_k=12, seed=7) if sampled else {}
        r = Request(0, prompt=list(prompt), max_new_tokens=max_new, **kw)
        eng.submit(r)
        eng.run(max_steps=300)
        assert r.done
        return r.out_tokens

    @pytest.mark.parametrize("sampled", [False, True],
                             ids=["greedy", "sampled"])
    def test_flip_on_off_on_token_identity(self, engine_setup, sampled):
        cfg, params = engine_setup
        rng = np.random.RandomState(21)
        prompt = list(rng.randint(1, 255, 16))
        max_new = 24
        ref = self._reference(cfg, params, prompt, max_new, sampled)

        # record the TRUE continuation so on-phase drafts accept, then
        # flip the EWMA: on (1.0) -> off (0.0) -> on (1.0)
        eng = ServingEngine(cfg, params, dp=1, b_local=1, max_len=64,
                            speculate=True, draft_len=4)
        _pin_costs(eng, {1: 1.25, 2: 1.5, 3: 1.75, 4: 2.0})
        key = eng.spec_store.key_of(prompt)
        eng.spec_store.record(key, tuple(prompt[len(key):]) + tuple(ref))
        kw = dict(temperature=0.9, top_k=12, seed=7) if sampled else {}
        r = Request(0, prompt=list(prompt), max_new_tokens=max_new, **kw)
        eng.submit(r)
        # timeline: steps 0-1 prefill the 16-token prompt, spec lanes
        # run from step 2 (full accepts: ~5 tokens/step), the off
        # window covers steps 4-5 (width-1 decode), then spec resumes
        phase_lanes = []
        steps = 0
        flips = {4: 0.0, 6: 1.0}
        while not eng.idle() and steps < 300:
            if steps in flips:
                eng.spec_store._accept[key] = flips[steps]
                phase_lanes.append(eng.stats["spec_lanes"])
            eng.step()
            steps += 1
        assert r.done
        assert r.out_tokens == ref, (
            "gating flip changed the token stream — a key index was "
            "skipped or reused at the flip boundary")
        # the flip really happened: lanes fired before the off-flip,
        # none during the off window, and again after the on-flip
        assert phase_lanes[0] > 0, "no spec lane before the off-flip"
        assert eng.stats["spec_lanes"] > phase_lanes[1], \
            "no spec lane after the on-flip"
        assert eng.stats["spec_gate_skips"] > 0, "off window never gated"
        assert eng.page_occupancy() == 0.0

    def test_flip_preserves_page_conservation(self, engine_setup):
        """Every step across both flip boundaries conserves pages and
        keeps §4.2 never-dry (the rollback plane is gating-oblivious)."""
        from repro.core import hier_pool
        cfg, params = engine_setup
        rng = np.random.RandomState(22)
        prompt = list(rng.randint(1, 255, 16))
        eng = ServingEngine(cfg, params, dp=1, b_local=1, max_len=64,
                            speculate=True, draft_len=4)
        _pin_costs(eng, {1: 1.25, 2: 1.5, 3: 1.75, 4: 2.0})
        ell = hier_pool.lane_ell(eng.state.pool.classes[0])
        key = eng.spec_store.key_of(prompt)
        eng.spec_store.record(key, tuple(prompt[len(key):])
                              + tuple(range(40, 60)))
        r = Request(0, prompt=list(prompt), max_new_tokens=10)
        eng.submit(r)
        flips = {3: 0.0, 6: 1.0}
        steps = 0
        while not eng.idle() and steps < 300:
            if steps in flips:
                eng.spec_store._accept[key] = flips[steps]
            eng.step()
            kv = eng.state.pool.classes[0]
            free_s = np.asarray(hier_pool.free_per_shard(kv))
            live_s = np.asarray(hier_pool.live_per_shard(kv))
            assert np.all(free_s + live_s == eng.pages_local)
            assert np.asarray(kv.private_top).min() >= ell
            steps += 1
        assert r.done
        assert eng.page_occupancy() == 0.0


# =================== 6. speculative-history checker across a gate flip

def _op(opid, name, pid=0, arg=None, result=None, t0=0, t1=1, meta=None):
    rec = OpRecord(opid=opid, pid=pid, name=name, arg=arg,
                   invoke_step=t0, result=result, response_step=t1)
    rec.meta.update(meta or {})
    return rec


class TestCheckerAcrossFlip:
    def test_flip_history_leak_free(self):
        """Spec episodes before and after a gated-off window (plain
        allocs in between) verify clean — the checker does not require
        episodes to be contiguous."""
        hist = [
            _op(1, "alloc_n", result=[4, 5, 6],
                meta={"spec": "e0", "shard": 0}),
            _op(2, "spec_rollback", arg=[5, 6], t0=2, t1=3,
                meta={"spec": "e0", "shard": 0, "kept": [4]}),
            # gate off: plain non-speculative allocation traffic
            _op(3, "alloc_n", result=[7], t0=4, t1=5),
            _op(4, "alloc_n", result=[8], t0=6, t1=7),
            # gate back on: a new episode on the same lane
            _op(5, "alloc_n", result=[9, 10], t0=8, t1=9,
                meta={"spec": "e1", "shard": 0}),
            _op(6, "spec_rollback", arg=[10], t0=10, t1=11,
                meta={"spec": "e1", "shard": 0, "kept": [9]}),
        ]
        assert check_speculative_history(hist) == []

    def test_flip_history_still_catches_leak(self):
        """The off-window must not mask a leak in the episode after the
        on-flip."""
        hist = [
            _op(1, "alloc_n", result=[4, 5, 6],
                meta={"spec": "e0", "shard": 0}),
            _op(2, "spec_rollback", arg=[5, 6], t0=2, t1=3,
                meta={"spec": "e0", "shard": 0, "kept": [4]}),
            _op(3, "alloc_n", result=[7], t0=4, t1=5),
            _op(4, "alloc_n", result=[9, 10, 11], t0=6, t1=7,
                meta={"spec": "e1", "shard": 0}),
            _op(5, "spec_rollback", arg=[10], t0=8, t1=9,
                meta={"spec": "e1", "shard": 0, "kept": [9]}),
        ]
        errs = check_speculative_history(hist)
        assert any("leak" in e and "11" in e for e in errs), errs


# ========================= 7. drafts riding mixed prompt/decode steps

class TestMixedStepDrafts:
    def test_mixed_step_token_identity(self, engine_setup):
        """A decode slot drafts while another slot is mid-prefill (the
        slimmed projection made that affordable); outputs match the
        non-speculative run of the same staggered schedule."""
        cfg, params = engine_setup
        rng = np.random.RandomState(23)
        p0 = list(rng.randint(1, 255, 16))
        p1 = list(rng.randint(1, 255, 24))

        def run(stream=None):
            eng = ServingEngine(cfg, params, dp=1, b_local=2, max_len=64,
                                chunk_size=4, speculate=stream is not None,
                                draft_len=3)
            if stream is not None:
                # drafts that match, whatever the random model emits:
                # r0's own non-speculative continuation
                key = eng.spec_store.key_of(p0)
                eng.spec_store.record(
                    key, tuple(p0[len(key):]) + tuple(stream))
            # long enough that r0 is still decoding when r1 arrives,
            # even with every draft accepted
            r0 = Request(0, prompt=list(p0), max_new_tokens=24)
            r1 = Request(1, prompt=list(p1), max_new_tokens=4)
            eng.submit(r0)
            # r0 prefills (and starts decoding) alone, then r1's long
            # prompt arrives: r0's decode rides r1's prefill steps
            for _ in range(6):
                eng.step()
            eng.submit(r1)
            eng.run(max_steps=300)
            assert r0.done and r1.done
            return [r0.out_tokens, r1.out_tokens], eng

        ref, _ = run()
        out, eng = run(stream=ref[0])
        assert out == ref, "a draft riding a prefill step changed tokens"
        assert eng.stats["spec_mixed_steps"] > 0, (
            "no draft ever rode a mixed prompt/decode step — the "
            "slimmed spec variant never exercised its prefill branch")
        assert eng.page_occupancy() == 0.0
