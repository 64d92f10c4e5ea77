"""Expert-paged MoE serving tests (DESIGN.md §15).

* Token identity: the expert-paged engine emits bit-identical streams
  to the resident-weight engine on every traffic mix — full residency,
  tight budgets with LRU eviction churn, restricted and unrestricted
  footprints — because the admitted-footprint router mask is applied
  pre-top_k in BOTH engines and the paged FFN reconstructs the exact
  dense weight stack from CLS_EXPERT pages.
* Read-only shared-page protocol under routing churn: a randomized
  load/admit/release/evict trace over expert-shaped CLS_EXPERT traffic
  replayed against the sequential :class:`repro.core.refpool.
  RefClassedPool` witness — identical grants, exact conformance,
  per-class conservation, and a non-negative §4.2 margin after
  eviction storms.
* Zero silent drops: the forward meters MoE capacity overflow
  (``moe_dropped_tokens`` rides the class-0 counter block) and the
  serving smokes assert it stays 0 at serving capacity factors.
* Observability: expert hit/miss/prefetch counters ride the expert
  class's ``_c2`` device-counter block through the step's one sync;
  ``expert_hit_rate`` exports through snapshot() and render_prom().
* Admission safety: the in-step miss row is an invariant 0 (residency
  is guaranteed before dispatch), unservable footprints reject as
  ``too_large``, and the engine stays leak-free after drain +
  ``flush_experts``.
"""

import dataclasses
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models
from repro.configs import get_config, smoke_config
from repro.core import classed_pool, refpool
from repro.core.classed_pool import ClassSpec
from repro.models.moe import moe_apply
from repro.models.transformer import EXPERT_PPE, expert_layer_slots
from repro.serving.engine import Request, ServingEngine, step_collectives
from repro.serving.telemetry import parse_prom


@pytest.fixture(scope="module")
def moe_setup():
    cfg = smoke_config(get_config("mixtral-8x7b"))
    # serving capacity factor: C clamps to top_k * tokens so the
    # expert-parallel dispatch drops nothing — the zero-drop invariant
    # the satellite meters guard (both engines use the same cf, so
    # identity is unaffected)
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=64.0))
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _drive(cfg, params, footprints, expert_paging, budget=None,
           n_req=6, max_new=5, **kw):
    eng = ServingEngine(cfg, params, dp=1, b_local=2, max_len=64,
                        prefix_sharing=False, mesh=None,
                        expert_paging=expert_paging,
                        expert_budget=budget, **kw)
    rng = np.random.RandomState(0)
    reqs = []
    for rid in range(n_req):
        prompt = list(rng.randint(1, cfg.vocab - 1, 6))
        r = Request(rid, prompt=prompt, max_new_tokens=max_new,
                    experts=footprints(rid))
        reqs.append(r)
        eng.submit(r)
    eng.run(max_steps=500)
    return eng, reqs


# ========================================================= token identity


def test_paged_vs_resident_token_identity(moe_setup):
    """Full-residency paged serving vs the resident engine on a mixed
    footprint trace: bit-identical tokens, zero dropped tokens, zero
    in-step misses (admission preloads every footprint), leak-free
    after drain + flush."""
    cfg, params = moe_setup
    fp = lambda rid: [(0, 1), None, (2, 3)][rid % 3]
    e0, r0 = _drive(cfg, params, fp, expert_paging=False)
    e1, r1 = _drive(cfg, params, fp, expert_paging=True)
    assert all(r.done for r in r0) and all(r.done for r in r1)
    assert [r.out_tokens for r in r0] == [r.out_tokens for r in r1]
    # satellite: the capacity-drop meter rode class 0's counter block
    # and stayed 0 — no silent token drops in either engine
    for eng in (e0, e1):
        assert int(eng.telemetry.shard["moe_dropped_tokens"].sum()) == 0
    # the miss row is an invariant detector: admission guarantees
    # residency, so a routed-to non-resident page is a bug
    assert int(e1.telemetry.shard["expert_miss_pages_c2"].sum()) == 0
    assert int(e1.telemetry.shard["expert_hit_pages_c2"].sum()) > 0
    # the in-scan gathers are metered as prefetch (overlapped loads)
    assert int(e1.telemetry.shard["expert_prefetch_pages_c2"].sum()) > 0
    assert e1.telemetry.expert_hit_rate() is not None
    assert e1.telemetry.never_dry_margin_min() >= 0
    e1.flush_experts()
    assert e1.leak_free()


def test_tight_budget_eviction_churn_identity(moe_setup):
    """A budget of one 2-expert footprint forces LRU churn between
    disjoint footprints: evictions happen, the resident peak respects
    the budget exactly, admission defers on the expert dimension, and
    the token streams stay identical to the resident engine."""
    cfg, params = moe_setup
    fp = lambda rid: (0, 1) if rid % 2 == 0 else (2, 3)
    budget = EXPERT_PPE * expert_layer_slots(cfg) * 2   # one footprint
    e0, r0 = _drive(cfg, params, fp, expert_paging=False)
    e1, r1 = _drive(cfg, params, fp, expert_paging=True, budget=budget)
    assert all(r.done for r in r1)
    assert [r.out_tokens for r in r0] == [r.out_tokens for r in r1]
    assert e1.stats["expert_evictions"] > 0
    assert e1.stats["expert_pages_resident_peak"] <= budget
    assert e1.scheduler.stats["defer_experts"] > 0
    assert int(e1.telemetry.shard["expert_miss_pages_c2"].sum()) == 0
    assert int(e1.telemetry.shard["moe_dropped_tokens"].sum()) == 0
    assert e1.telemetry.never_dry_margin_min() >= 0
    # per-class conservation on the live pool
    for c in range(e1.n_classes):
        free = np.asarray(classed_pool.free_per_shard(e1.state.pool, c))
        live = np.asarray(classed_pool.live_per_shard(e1.state.pool, c))
        nb = e1.state.pool.classes[c].shared.free_ids.shape[-1]
        assert int(free[0] + live[0]) == nb
    e1.flush_experts()
    assert e1.leak_free()


def test_unservable_footprint_rejected_too_large(moe_setup):
    """A footprint whose full-stack load exceeds the per-shard budget
    on an EMPTY shard can never be admitted — typed too_large
    rejection at submit, not a wedged queue."""
    cfg, params = moe_setup
    budget = EXPERT_PPE * expert_layer_slots(cfg) * 2
    eng = ServingEngine(cfg, params, dp=1, b_local=2, max_len=64,
                        prefix_sharing=False, mesh=None,
                        expert_paging=True, expert_budget=budget)
    big = Request(0, prompt=[3, 4, 5], max_new_tokens=3, experts=None)
    adm = eng.submit(big)
    assert not adm.accepted and adm.reason == "too_large"
    assert big.rejected == "too_large"
    ok = Request(1, prompt=[3, 4, 5], max_new_tokens=3, experts=(1, 2))
    assert eng.submit(ok).accepted
    eng.run(max_steps=200)
    assert ok.done
    eng.flush_experts()
    assert eng.leak_free()


# ===================================== shared-page protocol under churn


DP = 2
ESPEC = ClassSpec(page_size=64, num_blocks=30, num_lanes=2, ell=2)
SPECS = (ClassSpec(page_size=8, num_blocks=24, num_lanes=2, ell=2),
         ESPEC)
ECLS = 1        # the expert-like read-only class in this mini vector


def test_expert_refcount_churn_vs_witness():
    """Randomized routing-churn trace of the §15 residency protocol —
    bulk shared-stack loads (expert load), addref per admission,
    free_shared per release and per eviction — replayed in lockstep
    against the sequential RefClassedPool witness: identical grants,
    exact final-state conformance, conservation after every op, and a
    never-dry pool after eviction storms."""
    rng = random.Random(11)
    pool = classed_pool.create_dp(DP, SPECS)
    refs = refpool.create_classed_dp(DP, SPECS)
    # ledger[d]: expert -> (pages, batch_refs)
    ledger = [dict() for _ in range(DP)]
    next_eid = 0

    def conservation():
        for c, spec in enumerate(SPECS):
            free = np.asarray(classed_pool.free_per_shard(pool, c))
            live = np.asarray(classed_pool.live_per_shard(pool, c))
            for d in range(DP):
                assert free[d] + live[d] == spec.num_blocks

    for step in range(250):
        op = rng.choice(["load", "admit", "admit", "release", "release",
                         "evict", "evict_storm"])
        d = rng.randrange(DP)
        if op == "load":
            if sum(len(e[0]) for e in ledger[d].values()) + EXPERT_PPE \
                    > ESPEC.num_blocks - 3 * ESPEC.ell * ESPEC.num_lanes:
                continue        # admission respects the budget (§15)
            counts = np.zeros((DP, ESPEC.num_lanes), np.int32)
            counts[d, 0] = EXPERT_PPE
            pool, ids = classed_pool.alloc_from_shared_dp(
                pool, ECLS, jnp.asarray(counts), EXPERT_PPE)
            got = np.asarray(ids)
            for s in range(DP):
                ref_rows = refs[s].alloc_from_shared(
                    ECLS, counts[s], EXPERT_PPE)
                flat = [b for row in ref_rows for b in row]
                want = [int(x) for x in got[s].reshape(-1) if x >= 0]
                assert want == flat, f"shard {s}: load grant diverged"
            pages = [int(x) for x in got[d, 0] if x >= 0]
            assert len(pages) == EXPERT_PPE
            ledger[d][next_eid] = (pages, 0)
            next_eid += 1
        elif op == "admit" and ledger[d]:
            eid = rng.choice(list(ledger[d]))
            pages, b = ledger[d][eid]
            rows = np.full((DP, EXPERT_PPE), -1, np.int32)
            rows[d] = pages
            pool = classed_pool.addref_dp(pool, ECLS, jnp.asarray(rows))
            for s in range(DP):
                refs[s].addref(ECLS, [int(x) for x in rows[s]])
            ledger[d][eid] = (pages, b + 1)
        elif op == "release":
            hot = [e for e, (_, b) in ledger[d].items() if b > 0]
            if not hot:
                continue
            eid = rng.choice(hot)
            pages, b = ledger[d][eid]
            rows = np.full((DP, EXPERT_PPE), -1, np.int32)
            rows[d] = pages
            pool = classed_pool.free_shared_dp(pool, ECLS,
                                               jnp.asarray(rows))
            for s in range(DP):
                refs[s].free_shared(ECLS, [int(x) for x in rows[s]])
            ledger[d][eid] = (pages, b - 1)
        elif op in ("evict", "evict_storm"):
            # unpin-shaped eviction of COLD experts only; a storm
            # evicts every cold expert on the shard at once
            cold = [e for e, (_, b) in ledger[d].items() if b == 0]
            if not cold:
                continue
            victims = cold if op == "evict_storm" else [rng.choice(cold)]
            for eid in victims:
                pages, _ = ledger[d].pop(eid)
                rows = np.full((DP, EXPERT_PPE), -1, np.int32)
                rows[d] = pages
                pool = classed_pool.free_shared_dp(pool, ECLS,
                                                   jnp.asarray(rows))
                for s in range(DP):
                    refs[s].free_shared(ECLS, [int(x) for x in rows[s]])
        conservation()

    for d in range(DP):
        msg = refpool.conforms_classed(refs[d], pool, d)
        assert msg is None, f"shard {d}: {msg}"
    # after the storms, one rebalance restocks every lane to >= ell:
    # the class never went dry because churn respected the §15 budget
    pool = classed_pool.rebalance_dp(pool)
    for c in range(len(SPECS)):
        hp = pool.classes[c]
        margin = (np.asarray(hp.private_top).min()
                  - SPECS[c].ell)
        assert margin >= 0, f"class {c} ran a lane dry"


# =========================================================== drop meter


def test_moe_apply_meters_dropped_tokens(moe_setup):
    """The dispatch meter counts exactly the valid assignments dropped
    by capacity overflow: 0 at serving capacity factors, > 0 when the
    expert capacity C is squeezed below the routed load."""
    cfg, _ = moe_setup
    d, E, k = cfg.d_model, cfg.moe.num_experts, cfg.moe.top_k
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(16, 1, d).astype(np.float32))  # decode shape
    key = jax.random.PRNGKey(1)
    ffn = {
        "router": jax.random.normal(key, (d, E)) * 0.1,
        "w_gate": jax.random.normal(key, (E, d, cfg.d_ff)) * 0.05,
        "w_up": jax.random.normal(key, (E, d, cfg.d_ff)) * 0.05,
        "w_down": jax.random.normal(key, (E, cfg.d_ff, d)) * 0.05,
    }
    _, dropped, routed = moe_apply(cfg, ffn, x, metered=True)
    assert int(dropped.sum()) == 0, "serving cf must never drop tokens"
    assert int(routed.sum()) == 16 * k
    cfg_t = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=0.25))
    _, dropped_t, routed_t = moe_apply(cfg_t, ffn, x, metered=True)
    assert int(dropped_t.sum()) > 0, "squeezed capacity must meter drops"
    # conservation: every valid assignment is either kept or metered
    assert int(routed_t.sum()) + int(dropped_t.sum()) == 16 * k


# ======================================================== observability


def test_expert_counters_in_snapshot_and_prom(moe_setup):
    cfg, params = moe_setup
    fp = lambda rid: (0, 1) if rid % 2 == 0 else (2, 3)
    budget = EXPERT_PPE * expert_layer_slots(cfg) * 2
    eng, reqs = _drive(cfg, params, fp, expert_paging=True,
                       budget=budget)
    assert all(r.done for r in reqs)
    snap = eng.telemetry.snapshot()
    assert snap["expert_hit_rate"] is not None
    assert snap["counters"]["expert_load_pages"] > 0
    assert snap["counters"]["expert_pages_resident_peak"] == budget
    # the expert page meters ride the class-2 rows of the one-sync
    # counter block — per-shard sums land under the _c2 keys
    assert "expert_hit_pages_c2" in snap["per_shard"]
    assert sum(snap["per_shard"]["expert_hit_pages_c2"]) > 0
    assert sum(snap["per_shard"]["expert_miss_pages_c2"]) == 0
    text = eng.telemetry.render_prom()
    prom = parse_prom(text)
    assert prom["repro_expert_hit_rate"][()] >= 0
    assert prom["repro_expert_load_pages"][()] > 0
    assert sum(prom["repro_expert_hit_pages_c2"].values()) > 0
    assert prom["repro_moe_dropped_tokens"][(("shard", "0"),)] == 0
    eng.flush_experts()
    assert eng.leak_free()


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs >= 2 devices")
def test_one_collective_per_step_expert_paged(moe_setup):
    """The expert-paged serve variant on the dp-mesh plane compiles
    exactly one collective — expert gathers, footprint masking, and
    the §15 meter rows all ride inside the existing status
    all_gather."""
    cfg, params = moe_setup
    eng = ServingEngine(cfg, params, dp=2, b_local=2, max_len=64,
                        prefix_sharing=False, expert_paging=True)
    assert eng.mesh is not None
    n_gather, n_other = step_collectives(
        eng.compiled_step_text(eng.chunk, spec=False))
    assert n_gather == 1, f"expected exactly one all_gather: {n_gather}"
    assert n_other == 0, f"unexpected extra collectives: {n_other}"


def test_one_sync_per_step_expert_paged(moe_setup):
    """Expert paging adds no device->host syncs to the serve loop: one
    ``np.asarray`` per step, exactly like the dense engine (loads and
    refcount traffic are jitted dispatches, never reads)."""
    cfg, params = moe_setup
    budget = EXPERT_PPE * expert_layer_slots(cfg) * 2   # one footprint
    eng = ServingEngine(cfg, params, dp=1, b_local=2, max_len=64,
                        prefix_sharing=False, mesh=None,
                        expert_paging=True, expert_budget=budget)
    rng = np.random.RandomState(5)
    for rid in range(4):
        # short streams + a one-footprint budget: slots turn over
        # INSIDE the patched window, so release (bulk free_shared),
        # re-admission (addref), eviction AND reload traffic all run
        # under the sync counter
        eng.submit(Request(rid, prompt=list(rng.randint(1, 255, 6)),
                           max_new_tokens=2,
                           experts=(0, 1) if rid % 2 else (2, 3)))
    eng.step()                       # admission + first loads

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

    loads_before = eng.stats["expert_load_pages"]
    steps_before = eng.stats["steps"]
    orig = engine_mod.np
    engine_mod.np = CountingNp()
    try:
        for _ in range(5):           # may drain early: idle fast-path
            eng.step()               # steps skip the dispatch AND sync
    finally:
        engine_mod.np = orig
    served = eng.stats["steps"] - steps_before
    assert served >= 3, "window too short to cover slot turnover"
    assert len(syncs) == served, f"1 sync per served step: {syncs}"
    assert eng.stats["expert_load_pages"] > loads_before, (
        "the patched window never exercised the expert load path")


# ====================================================== fault tolerance


def test_recover_inplace_reloads_experts(moe_setup):
    """In-place recovery reclaims every CLS_EXPERT page (tables NULL,
    ledger cleared) and the requeued requests re-admit with fresh
    loads — the engine drains token-identically and leak-free."""
    cfg, params = moe_setup
    fp = lambda rid: (0, 1) if rid % 2 == 0 else (2, 3)
    e0, r0 = _drive(cfg, params, fp, expert_paging=False)
    eng = ServingEngine(cfg, params, dp=1, b_local=2, max_len=64,
                        prefix_sharing=False, mesh=None,
                        expert_paging=True)
    rng = np.random.RandomState(0)
    reqs = []
    for rid in range(6):
        prompt = list(rng.randint(1, cfg.vocab - 1, 6))
        r = Request(rid, prompt=prompt, max_new_tokens=5,
                    experts=fp(rid))
        reqs.append(r)
        eng.submit(r)
    for _ in range(3):
        eng.step()
    loads_before = eng.stats["expert_load_pages"]
    assert loads_before > 0
    eng._recover_inplace()
    assert eng.expert_ledger.resident_count() == 0
    for tab in eng.state.expert_tables.values():
        assert int(jnp.max(tab)) < 0, "recovery left a mapped expert"
    assert bool(jnp.all(eng.expert_mask)), "recovery left a stale mask"
    eng.run(max_steps=500)
    assert all(r.done for r in reqs)
    # resumed streams are the streams the unpreempted run produced
    assert [r.out_tokens for r in reqs] == [r.out_tokens for r in r0]
    assert eng.stats["expert_load_pages"] > loads_before, "reloaded"
    eng.flush_experts()
    assert eng.leak_free()
