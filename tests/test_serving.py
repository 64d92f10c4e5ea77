"""Serving engine tests: continuous batching + allocator integration."""

from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import models
from repro.configs import get_config, smoke_config
from repro.serving.engine import Request, ServingEngine


@pytest.fixture(scope="module")
def engine_setup():
    cfg = smoke_config(get_config("olmo-1b"))
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_continuous_batching_completes(engine_setup):
    cfg, params = engine_setup
    eng = ServingEngine(cfg, params, dp=2, b_local=2, max_len=64)
    rng = np.random.RandomState(0)
    reqs = [Request(i, prompt=list(rng.randint(1, 255, rng.randint(3, 10))),
                    max_new_tokens=5) for i in range(9)]
    for r in reqs:
        eng.submit(r)
    eng.run(max_steps=500)
    assert all(r.done for r in reqs)
    assert all(len(r.out_tokens) == 5 for r in reqs)
    # oversubscribed queue (9 reqs, 4 slots) => continuous batching worked
    assert eng.stats["admitted"] == 9


def test_no_page_leaks(engine_setup):
    cfg, params = engine_setup
    eng = ServingEngine(cfg, params, dp=1, b_local=2, max_len=48)
    for i in range(6):
        eng.submit(Request(i, prompt=[1, 2, 3, 4, 5], max_new_tokens=4))
    eng.run(max_steps=300)
    assert eng.page_occupancy() == 0.0, "pages leaked after drain"


def test_host_allocator_constant_time(engine_setup):
    """Admission cost through the paper's allocator is O(1) and the
    simulated allocator reports no safety violations."""
    cfg, params = engine_setup
    eng = ServingEngine(cfg, params, dp=1, b_local=2, max_len=48,
                        scheduler_lanes=3)
    for i in range(12):
        eng.submit(Request(i, prompt=[2, 3], max_new_tokens=3))
    eng.run(max_steps=300)
    assert eng.stats["alloc_steps_max"] <= 70       # O(1) bound (cf. tests/core)
    assert eng.lane_ctx.violations == []


@pytest.mark.parametrize("arch", [
    "olmo-1b",              # pure paged-global attention
    "recurrentgemma-2b",    # ring (sliding window) + rglru recurrent
    "mamba2-370m",          # ssd recurrent
])
def test_decode_step_chunk_matches_single_token(arch):
    """Model-level contract: decode_step_chunk over ragged chunks yields
    the same per-position logits as token-by-token decode_step — across
    the paged, ring-eviction, and recurrent-scan chunk paths."""
    cfg = smoke_config(get_config(arch))
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    from repro.models.decode_init import empty_decode_state
    rng = np.random.RandomState(1)
    toks = rng.randint(1, 255, (1, 2, 11)).astype(np.int32)

    s1 = empty_decode_state(cfg, 1, 2, 64)
    outs1 = []
    for t in range(11):
        lg, s1 = models.decode_step(cfg, params, jnp.asarray(toks[:, :, t]),
                                    s1)
        outs1.append(np.asarray(lg))
    outs1 = np.stack(outs1, axis=2)

    s2 = empty_decode_state(cfg, 1, 2, 64)
    outs2 = []
    for c0 in range(0, 11, 4):           # 11 = 4 + 4 + 3, ragged tail
        n = min(4, 11 - c0)
        chunk = np.zeros((1, 2, 4), np.int32)
        chunk[:, :, :n] = toks[:, :, c0:c0 + n]
        lg, s2, ok = models.decode_step_chunk(
            cfg, params, jnp.asarray(chunk), s2,
            jnp.full((1, 2), n, jnp.int32))
        assert np.asarray(ok).all()
        outs2.append(np.asarray(lg)[:, :, :n])
    outs2 = np.concatenate(outs2, axis=2)

    np.testing.assert_allclose(outs1, outs2, atol=1e-5, rtol=1e-5)
    assert np.array_equal(np.asarray(s1.seq_lens), np.asarray(s2.seq_lens))
    kv1, kv2 = s1.pool.classes[0], s2.pool.classes[0]
    assert np.array_equal(np.asarray(kv1.private_top),
                          np.asarray(kv2.private_top))
    assert np.array_equal(np.asarray(kv1.shared.top),
                          np.asarray(kv2.shared.top))


def test_decode_step_loop_survives_lane_exhaustion(engine_setup):
    """Regression (review finding): raw decode_step loops have no
    per-step rebalance, so once a slot's lane (ell warm pages) is spent
    the allocator must fall back to the shared pool — not write -1 into
    the page table and silently corrupt KV.  30 tokens = 4 pages at
    psz=8, twice the ell=2 lane stock."""
    cfg, params = engine_setup
    from repro.core import classed_pool, hier_pool
    from repro.models.decode_init import empty_decode_state
    rng = np.random.RandomState(5)
    toks = rng.randint(1, 255, (1, 2, 30)).astype(np.int32)

    s1 = empty_decode_state(cfg, 1, 2, 64)          # never rebalanced
    s2 = empty_decode_state(cfg, 1, 2, 64)          # rebalanced per step
    outs1, outs2 = [], []
    for t in range(30):
        lg, s1 = models.decode_step(cfg, params, jnp.asarray(toks[:, :, t]),
                                    s1)
        outs1.append(np.asarray(lg))
        lg, s2 = models.decode_step(cfg, params, jnp.asarray(toks[:, :, t]),
                                    s2)
        s2 = s2._replace(pool=classed_pool.rebalance_dp(s2.pool))
        outs2.append(np.asarray(lg))
    np.testing.assert_allclose(np.stack(outs1), np.stack(outs2),
                               atol=1e-5, rtol=1e-5)
    # all written pages mapped, none through a clamped NULL entry
    assert np.all(np.asarray(s1.page_tables)[:, :, :4] >= 0)
    kv = s1.pool.classes[0]
    total = kv.shared.free_ids.shape[1]
    free = int(hier_pool.total_free(kv))
    assert free + int(hier_pool.num_live(kv)) == total


def test_decode_step_chunk_pool_denial_appends_nothing(engine_setup):
    """Pool exhaustion is all-or-nothing: a chunk whose pages cannot all
    be granted must not advance seq_lens (silently attending over
    never-written positions) and must report ok=False."""
    cfg, params = engine_setup
    from repro.models.decode_init import empty_decode_state
    state = empty_decode_state(cfg, 1, 1, 64)
    # drain the slot lanes AND the shared pool: a chunk must be denied
    kv = state.pool.classes[0]
    kv = kv._replace(
        private_top=jnp.zeros_like(kv.private_top),
        shared=kv.shared._replace(top=jnp.zeros_like(kv.shared.top)))
    state = state._replace(pool=state.pool._replace(
        classes=(kv,) + state.pool.classes[1:]))
    toks = jnp.ones((1, 1, 8), jnp.int32)
    _, state, ok = models.decode_step_chunk(
        cfg, params, toks, state, jnp.full((1, 1), 8, jnp.int32))
    assert not bool(ok[0, 0])
    assert int(state.seq_lens[0, 0]) == 0
    assert np.all(np.asarray(state.page_tables) == -1)


def test_capacity_cap_when_max_len_not_page_multiple(engine_setup):
    """max_len not a multiple of page_size: sequences must stop at the
    page-table capacity instead of overwriting live KV through the
    clamped page index (regression for the chunked path)."""
    cfg, params = engine_setup          # smoke page_size = 8
    eng = ServingEngine(cfg, params, dp=1, b_local=2, max_len=44,
                        chunk_size=8)
    assert eng.capacity == 40           # 5 pages of 8
    reqs = [Request(i, prompt=[2] * 30, max_new_tokens=64)
            for i in range(2)]
    for r in reqs:
        eng.submit(r)
    eng.run(max_steps=200)
    assert all(r.done for r in reqs)
    # 30 prompt + 10 generated hits capacity-1 done detection
    assert all(len(r.out_tokens) <= 10 for r in reqs)
    assert eng.page_occupancy() == 0.0


def test_token_identity_across_lane_widths(engine_setup):
    """The unified token-lane step must emit exactly the same tokens at
    every lane width — chunk_size=1 IS the pre-refactor single-token
    baseline (a width-1 lane per slot per step), so this is the
    legacy-deletion identity bar: greedy decode bit-identical across
    ragged prompt lengths, continuous batching, and chunk sizes."""
    cfg, params = engine_setup
    rng = np.random.RandomState(42)
    prompts = [list(rng.randint(1, 255, rng.randint(2, 29)))
               for _ in range(9)]

    def run(**kw):
        eng = ServingEngine(cfg, params, dp=2, b_local=2, max_len=64, **kw)
        reqs = [Request(i, prompt=list(p), max_new_tokens=4)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run(max_steps=500)
        assert all(r.done for r in reqs)
        return [r.out_tokens for r in reqs], eng

    width1_out, width1_eng = run(chunk_size=1)
    for chunk in (4, 16):
        out, eng = run(chunk_size=chunk)
        assert out == width1_out, f"chunk_size={chunk} diverged"
        assert eng.page_occupancy() == 0.0
    # chunked prefill takes fewer steps than one-token-per-step
    out16, eng16 = run(chunk_size=16)
    assert eng16.stats["steps"] < width1_eng.stats["steps"]


def test_steady_state_decode_single_sync(engine_setup):
    """Once prompts are consumed, each engine step performs exactly one
    device->host sync (the packed status array) and runs at T=1."""
    cfg, params = engine_setup
    eng = ServingEngine(cfg, params, dp=1, b_local=2, max_len=64,
                        chunk_size=8)
    for i in range(2):
        eng.submit(Request(i, prompt=[3, 5, 7], max_new_tokens=8))
    eng.step()                      # prefill chunk consumes the prompts
    assert all(not p for p in eng.pending_tokens.values())

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
        for _ in range(3):
            eng.step()
    finally:
        engine_mod.np = orig
    assert eng.stats["steps"] == steps0 + 3
    assert len(syncs) == 3, f"expected 1 sync/step, saw {syncs}"
    from repro.serving.telemetry import N_CTR
    assert all(s == (4 + N_CTR, 1, 2) for s in syncs), \
        "sync is the packed status (+ telemetry counter rows)"


def test_eos_stops_generation(engine_setup):
    """On-device EOS detection finishes a request mid-budget."""
    cfg, params = engine_setup
    probe = ServingEngine(cfg, params, dp=1, b_local=1, max_len=64)
    r0 = Request(0, prompt=[5, 9, 17, 3], max_new_tokens=6)
    probe.submit(r0)
    probe.run(max_steps=100)
    assert len(r0.out_tokens) == 6
    eos = r0.out_tokens[2]          # greedy decode is deterministic
    first = r0.out_tokens.index(eos)      # eos may repeat earlier

    eng = ServingEngine(cfg, params, dp=1, b_local=1, max_len=64,
                        eos_id=eos)
    r1 = Request(0, prompt=[5, 9, 17, 3], max_new_tokens=6)
    eng.submit(r1)
    eng.run(max_steps=100)
    assert r1.done
    assert r1.out_tokens == r0.out_tokens[:first + 1]
    assert eng.page_occupancy() == 0.0


def test_sampling_determinism(engine_setup):
    """Temperature/top-k sampling (the on-device sampler that replaced
    the hardcoded argmax) is keyed per request by (seed, position):
    same seed → identical streams, different seed → different streams,
    and the draw is invariant to chunk size / batch composition.
    temperature=0 (the default) stays greedy and seed-independent, so
    every token-identity test in this file is unaffected."""
    cfg, params = engine_setup

    def run(seed, temp=0.9, topk=8, chunk=8):
        eng = ServingEngine(cfg, params, dp=1, b_local=2, max_len=64,
                            chunk_size=chunk)
        reqs = [Request(i, prompt=[3 + i, 5, 7, 11], max_new_tokens=6,
                        temperature=temp, top_k=topk, seed=seed + i)
                for i in range(3)]
        for r in reqs:
            eng.submit(r)
        eng.run(max_steps=200)
        assert all(r.done for r in reqs)
        assert eng.page_occupancy() == 0.0
        return [r.out_tokens for r in reqs]

    a = run(42)
    assert a == run(42), "same seed must reproduce"
    assert a != run(43), "different seed must diverge"
    assert a == run(42, chunk=4), "sampling must be chunk-invariant"
    assert run(0, temp=0.0) == run(99, temp=0.0), \
        "greedy must ignore the seed"


def test_outputs_match_offline_decode(engine_setup):
    """Engine output == running the same prompt through raw decode."""
    cfg, params = engine_setup
    prompt = [5, 9, 17, 3]
    n_new = 4

    eng = ServingEngine(cfg, params, dp=1, b_local=1, max_len=64)
    req = Request(0, prompt=list(prompt), max_new_tokens=n_new)
    eng.submit(req)
    eng.run(max_steps=100)

    # offline: token-by-token greedy decode from an empty state
    from repro.models.decode_init import empty_decode_state
    state = empty_decode_state(cfg, 1, 1, 64)
    toks = list(prompt)
    out = []
    for t in range(len(prompt) + n_new - 1):
        tok = jnp.asarray([[toks[t] if t < len(toks) else out[-1]]],
                          jnp.int32)
        logits, state = models.decode_step(cfg, params, tok, state)
        if t >= len(prompt) - 1:
            nxt = int(jnp.argmax(logits[0, 0]))
            out.append(nxt)
            if t >= len(toks) - 1:
                toks.append(nxt)
    assert req.out_tokens == out[:n_new]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_serve_main_returns_served_requests(monkeypatch, tmp_path, dtype):
    """The serving entry point hands back its engine and requests, serves
    at the asked dtype, and retries no step in a plain run."""
    from repro.launch import serve
    # set from outside, the cache is JAX's own business: nothing in code
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    eng, reqs = serve.main(["--arch", "olmo-1b", "--smoke", "--dtype", dtype,
                            "--requests", "5", "--max-new", "3",
                            "--dp", "1", "--b-local", "2"])
    assert eng.cfg.dtype == dtype
    assert eng.max_restarts == 0
    assert [r.rid for r in reqs] == list(range(5))
    assert all(r.done and len(r.out_tokens) == 3 for r in reqs)
    assert eng.page_occupancy() == 0.0


def test_compile_cache_dir(monkeypatch, tmp_path):
    from repro.launch import compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.init_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    fixed = compile_cache.init_compile_cache()
    assert fixed == str(compile_cache.DEFAULT_DIR)
    # the checkout, found from the module's own path
    assert compile_cache.DEFAULT_DIR == (
        Path(__file__).resolve().parents[1] / ".jax_cache")
    assert updates == [("jax_compilation_cache_dir", fixed)]
