"""Multi-host pool test plane: shard_map conformance + cross-shard safety.

The tentpole deliverable of the mesh lift (DESIGN.md §9), in four
layers, all runnable on CPU — under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` the pool ops and
the serving engine run shard_mapped over a real ("dp",) device mesh
(CI's mesh-8 job); on a single device the same tests cover the vmap
semantics, which must be bit-identical.

1. **Differential conformance**: one randomized op trace replayed
   through the jax ``HierPool`` (shard_mapped when a mesh exists) and
   through the host-side sequential reference model
   (:mod:`repro.core.refpool`, the P-SIM sequential witness) — grant
   ids and final pool state must match exactly per shard, hence
   identical grant/free multisets per shard.
2. **Cross-shard adversarial storms**: per-shard lanes and per-shard
   rebalancers interleaved instruction-by-instruction (torn
   drain/refill windows straddling other shards' ops), histories
   checked with the sharded linearizability extensions
   (``split_history_by_shard`` + cross-shard theft) plus per-shard
   conservation; crash variants included.
3. **Engine property storms** (seeded, via the hypothesis shim):
   admission -> prefill -> preempt -> release traffic on a dp=4 engine,
   asserting per-shard page conservation, the §4.2 never-dry invariant
   per lane, and token-identity vs the single-device (dp=1) run of the
   same trace.
4. **Mesh plumbing**: the engine builds the mesh, shards its state over
   it, and still performs exactly one device->host sync per step.
"""

import random

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from jax.sharding import NamedSharding, PartitionSpec as P

from repro import models
from repro.configs import get_config, smoke_config
from repro.core import (Scheduler, SimContext, check_cross_shard_frees,
                        check_sharded_batch_history, hier_pool, refpool,
                        split_history_by_shard)
from repro.core.sim import OpRecord
from repro.launch.mesh import make_dp_mesh
from repro.models.transformer import pool_ell
from repro.serving.engine import Request, ServingEngine
from repro.serving.sched import SchedConfig

DP, LANES, ELL, KMAX, BLOCKS = 4, 3, 2, 3, 64


def _pool_ops(mesh, pool):
    """Jitted DP pool ops — shard_mapped over the mesh when one exists,
    plain jit (vmap semantics) otherwise.  Same call signatures."""
    specs = jax.tree.map(lambda _: P("dp"), pool)

    def w(fn, out_specs):
        if mesh is None:
            return jax.jit(fn)
        return jax.jit(jax.shard_map(fn, mesh=mesh,
                                     in_specs=(specs, P("dp")),
                                     out_specs=out_specs, check_vma=False))

    def reb(p, _):
        return hier_pool.rebalance_dp(p), _

    return {
        "alloc": w(hier_pool.alloc_dp, (specs, P("dp"))),
        "alloc_n": w(lambda p, c: hier_pool.alloc_n_dp(p, c, KMAX),
                     (specs, P("dp"))),
        "alloc_shared": w(
            lambda p, c: hier_pool.alloc_from_shared_dp(p, c, KMAX),
            (specs, P("dp"))),
        "addref": w(hier_pool.addref_dp, specs),
        "free_n": w(hier_pool.free_n_dp, specs),
        "free_shared": w(hier_pool.free_shared_dp, specs),
        "rebalance": w(reb, (specs, P("dp"))),
    }


# module-level lazy context (NOT a pytest fixture: the hypothesis
# fallback shim's @given wrapper hides the test signature, so fixtures
# cannot be injected into property tests — plain helpers work in both)
_POOL_CTX = None


def _get_pool_ctx():
    global _POOL_CTX
    if _POOL_CTX is None:
        mesh = make_dp_mesh(DP)
        pool = hier_pool.create_dp(DP, BLOCKS, LANES, ELL)
        if mesh is not None:
            pool = jax.device_put(
                pool,
                jax.tree.map(lambda _: NamedSharding(mesh, P("dp")), pool))
        _POOL_CTX = (mesh, pool, _pool_ops(mesh, pool))
    return _POOL_CTX


# ===================================================== 1. conformance

class TestDifferentialConformance:
    """One trace, three executors: jax (shard_map or vmap) vs the
    host-side sequential reference — identical grants, identical final
    stacks/refcounts per shard."""

    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 9999))
    def test_trace_conforms_per_shard(self, seed):
        mesh, pool0, ops = _get_pool_ctx()
        rng = random.Random(seed)
        pool = pool0
        refs = refpool.create_dp(DP, BLOCKS, LANES, ELL)
        # per-shard bookkeeping for building valid frees: blocks the
        # user holds (one base ref) and blocks carrying an extra ref
        held = [[] for _ in range(DP)]
        extra = [[] for _ in range(DP)]
        jax_grants = [[] for _ in range(DP)]    # grant multiset (device)
        ref_grants = [[] for _ in range(DP)]    # grant multiset (spec)
        frees = [[] for _ in range(DP)]         # free multiset (shared)
        addrefs = [0] * DP                      # extra refs ever taken

        def pad(row, k):
            return row + [-1] * (k - len(row))

        for step in range(40):
            op = rng.choice(["alloc", "alloc_n", "alloc_shared", "addref",
                             "free_n", "free_n", "free_shared",
                             "rebalance"])
            if op == "alloc":
                want = np.asarray(
                    [[rng.random() < 0.7 for _ in range(LANES)]
                     for _ in range(DP)])
                pool, ids = ops["alloc"](pool, jnp.asarray(want))
                got = np.asarray(ids)
                for d in range(DP):
                    ref_ids = refs[d].alloc(want[d])
                    assert got[d].tolist() == ref_ids, (
                        f"seed {seed} step {step} shard {d}: alloc")
                    jax_grants[d] += [int(b) for b in got[d] if b >= 0]
                    valid = [b for b in ref_ids if b >= 0]
                    held[d] += valid
                    ref_grants[d] += valid
            elif op == "alloc_n":
                counts = np.asarray(
                    [[rng.randint(0, KMAX) for _ in range(LANES)]
                     for _ in range(DP)], np.int32)
                pool, ids = ops["alloc_n"](pool, jnp.asarray(counts))
                got = np.asarray(ids)
                for d in range(DP):
                    ref_rows = refs[d].alloc_n(counts[d], KMAX)
                    jax_grants[d] += [int(b) for b in got[d].ravel()
                                      if b >= 0]
                    for ln in range(LANES):
                        assert got[d, ln].tolist() == pad(ref_rows[ln],
                                                          KMAX), (
                            f"seed {seed} step {step} shard {d}: alloc_n")
                        held[d] += ref_rows[ln]
                        ref_grants[d] += ref_rows[ln]
            elif op == "alloc_shared":
                counts = np.asarray(
                    [[rng.randint(0, 2) for _ in range(LANES)]
                     for _ in range(DP)], np.int32)
                pool, ids = ops["alloc_shared"](pool, jnp.asarray(counts))
                got = np.asarray(ids)
                for d in range(DP):
                    ref_rows = refs[d].alloc_from_shared(counts[d], KMAX)
                    jax_grants[d] += [int(b) for b in got[d].ravel()
                                      if b >= 0]
                    for ln in range(LANES):
                        assert got[d, ln].tolist() == pad(ref_rows[ln],
                                                          KMAX), (
                            f"seed {seed} step {step} shard {d}: bulk")
                        held[d] += ref_rows[ln]
                        ref_grants[d] += ref_rows[ln]
            elif op == "addref":
                rows = []
                for d in range(DP):
                    picks = ([rng.choice(held[d])] if held[d]
                             and rng.random() < 0.8 else [])
                    extra[d] += picks
                    addrefs[d] += len(picks)
                    refs[d].addref(pad(picks, 1))
                    rows.append(pad(picks, 1))
                pool = ops["addref"](pool, jnp.asarray(rows, jnp.int32))
            elif op == "free_n":
                rows_dp = []
                for d in range(DP):
                    rows = [[] for _ in range(LANES)]
                    k = rng.randint(0, min(3, len(held[d])))
                    for _ in range(k):
                        b = held[d].pop(rng.randrange(len(held[d])))
                        rows[rng.randrange(LANES)].append(b)
                        frees[d].append(b)
                    rows_dp.append([pad(r, KMAX) for r in rows])
                pool = ops["free_n"](pool, jnp.asarray(rows_dp, jnp.int32))
                for d in range(DP):
                    refs[d].free_n(rows_dp[d])
            elif op == "free_shared":
                rows = []
                for d in range(DP):
                    picks = []
                    if extra[d] and rng.random() < 0.8:
                        picks.append(extra[d].pop())
                        frees[d].append(picks[-1])
                    rows.append(pad(picks, 1))
                pool = ops["free_shared"](pool, jnp.asarray(rows, jnp.int32))
                for d in range(DP):
                    refs[d].free_shared(rows[d])
            else:
                pool, _ = ops["rebalance"](pool, jnp.zeros((DP, 1),
                                                           jnp.int32))
                for d in range(DP):
                    refs[d].rebalance()

            # shard-resolved conservation at every step
            free_s = np.asarray(hier_pool.free_per_shard(pool))
            live_s = np.asarray(hier_pool.live_per_shard(pool))
            for d in range(DP):
                assert free_s[d] + live_s[d] == BLOCKS, (
                    f"seed {seed} step {step} shard {d}: conservation")

        # identical grant/free multisets per shard: frees are the same
        # trace input on both sides by construction, grants compared
        # here as whole multisets (and per-op exactly, above), and the
        # exact final-state conformance closes the loop
        sh = jax.tree.map(np.asarray, pool)
        for d in range(DP):
            assert sorted(jax_grants[d]) == sorted(ref_grants[d]), (
                f"seed {seed} shard {d}: grant multisets diverge")
            msg = refpool.conforms(
                refs[d], sh.shared.free_ids[d], sh.shared.top[d],
                sh.private_ids[d], sh.private_top[d],
                sh.shared.refcount[d])
            assert msg is None, f"seed {seed} shard {d}: {msg}"
            # every free returns one reference: a grant or an addref
            assert len(frees[d]) <= len(ref_grants[d]) + addrefs[d]

    def test_shard_map_matches_vmap_exactly(self):
        """When a mesh exists, the shard_mapped ops and the plain vmap
        ops must produce bit-identical pools and grants for the same
        trace — the mesh changes placement, never results."""
        mesh, pool0, ops = _get_pool_ctx()
        if mesh is None:
            pytest.skip("needs >= 4 devices (mesh-8 CI job)")
        vops = _pool_ops(None, pool0)
        p_a = pool0
        p_b = jax.device_put(pool0,
                             jax.devices()[0])     # single-device copy
        rng = random.Random(123)
        for _ in range(12):
            counts = jnp.asarray(
                [[rng.randint(0, KMAX) for _ in range(LANES)]
                 for _ in range(DP)], jnp.int32)
            p_a, ids_a = ops["alloc_n"](p_a, counts)
            p_b, ids_b = vops["alloc_n"](p_b, counts)
            assert np.array_equal(np.asarray(ids_a), np.asarray(ids_b))
            p_a = ops["free_n"](p_a, ids_a)
            p_b = vops["free_n"](p_b, ids_b)
            p_a, _ = ops["rebalance"](p_a, jnp.zeros((DP, 1), jnp.int32))
            p_b, _ = vops["rebalance"](p_b, jnp.zeros((DP, 1), jnp.int32))
        for leaf_a, leaf_b in zip(jax.tree.leaves(p_a), jax.tree.leaves(p_b)):
            assert np.array_equal(np.asarray(leaf_a), np.asarray(leaf_b))


# ============================================= 2. cross-shard storms

class TestCrossShardStorms:
    """Adversarial interleavings across shard-local pools: lanes and a
    torn-rebalance program per shard, scheduled instruction-by-
    instruction so one shard's drain/refill window straddles other
    shards' ops.  Histories carry meta["shard"] and must pass the
    sharded checks; per-shard conservation holds even under crashes."""

    N_SHARDS = 3

    def _storm(self, seed, crash_rebalancer=None, crash_lane=None):
        S, L, ell, kmax = self.N_SHARDS, 2, 3, 3
        pools = {d: hier_pool.create(num_blocks=48, num_lanes=L, ell=ell)
                 for d in range(S)}
        held = {(d, ln): [] for d in range(S) for ln in range(L)}
        ctx = SimContext(S * (L + 1), seed=seed)
        sched = Scheduler(seed=seed)

        def lane_program(d, ln, pid):
            rng = random.Random(seed * 101 + pid)
            mine = held[(d, ln)]
            for _ in range(20):
                yield
                if not mine or rng.random() < 0.55:
                    want = rng.randint(1, kmax)
                    counts = np.zeros(L, np.int32)
                    counts[ln] = want
                    rec = ctx.begin_op(pid, "alloc_n", arg=want)
                    rec.meta["shard"] = d
                    rec.invoke_step = sched.steps
                    yield
                    pool, ids = hier_pool.alloc_n(
                        pools[d], jnp.asarray(counts), kmax)
                    pools[d] = pool
                    got = [int(i) for i in np.asarray(ids)[ln] if i >= 0]
                    mine.extend(got)
                    yield
                    ctx.end_op(rec, result=got)
                    rec.response_step = sched.steps
                else:
                    k = rng.randint(1, min(len(mine), kmax))
                    back = mine[-k:]
                    ids = np.full((L, kmax), -1, np.int32)
                    ids[ln, :k] = back
                    rec = ctx.begin_op(pid, "free_n", arg=back)
                    rec.meta["shard"] = d
                    rec.invoke_step = sched.steps
                    yield
                    pools[d] = hier_pool.free_n(pools[d], jnp.asarray(ids))
                    del mine[-k:]
                    yield
                    ctx.end_op(rec)
                    rec.response_step = sched.steps

        def rebalancer(d, pid):
            for _ in range(30):
                yield
                pools[d] = hier_pool.rebalance_drain(pools[d])
                yield          # torn window: other SHARDS run here too
                pools[d] = hier_pool.rebalance_refill(pools[d])

        pid = 0
        reb_pids = {}
        for d in range(S):
            for ln in range(L):
                sched.add(pid, lane_program(d, ln, pid))
                pid += 1
            reb_pids[d] = pid
            sched.add(pid, rebalancer(d, pid))
            pid += 1
        crash_at = {}
        if crash_rebalancer is not None:
            d, at = crash_rebalancer
            crash_at[reb_pids[d]] = at
        if crash_lane is not None:
            crash_at[crash_lane] = 150
        sched.run("bursty", crash_at=crash_at)

        errs = check_sharded_batch_history(ctx.history)
        assert errs == [], errs
        by_shard = split_history_by_shard(ctx.history)
        assert set(by_shard) <= set(range(S))
        for d in range(S):
            live = sum(len(held[(d, ln)]) for ln in range(L))
            free = int(hier_pool.total_free(pools[d]))
            assert free + live == 48, (
                f"shard {d}: blocks lost or duplicated")
            assert int(hier_pool.num_live(pools[d])) == live

    def test_interleaved_rebalance_across_shards(self):
        for seed in (0, 1, 2):
            self._storm(seed)

    def test_crash_mid_rebalance_one_shard(self):
        """One shard's rebalancer dies inside its torn window while the
        other shards keep trading: only that shard's drained batch is
        parked on its own shared stack; every shard conserves."""
        self._storm(seed=4, crash_rebalancer=(1, 120))

    def test_crash_lane_holding_blocks(self):
        self._storm(seed=6, crash_lane=2)

    def test_checker_catches_cross_shard_theft(self):
        """Self-test: a block granted on shard 0 but freed through
        shard 1's history is flagged as theft by the checker (and the
        same history with the right shard tag passes)."""
        def hist(free_shard):
            a = OpRecord(opid=0, pid=0, name="alloc_n", arg=2,
                         invoke_step=0, result=[5, 6], response_step=1)
            a.meta["shard"] = 0
            f = OpRecord(opid=1, pid=1, name="free_n", arg=[5, 6],
                         invoke_step=2, result=None, response_step=3)
            f.meta["shard"] = free_shard
            return [a, f]

        errs = check_cross_shard_frees(hist(free_shard=1))
        assert len(errs) == 2 and all("theft" in e for e in errs), errs
        assert check_cross_shard_frees(hist(free_shard=0)) == []
        # ...and a same-id grant on ANOTHER shard is not a false theft
        h = hist(free_shard=0)
        b = OpRecord(opid=2, pid=2, name="alloc_n", arg=2, invoke_step=0,
                     result=[5, 6], response_step=1)
        b.meta["shard"] = 1
        errs = check_sharded_batch_history(h + [b])
        assert errs == [], errs


# ========================================== 3. engine property storms

_ENGINE_CTX = None


def _get_engine_setup():
    global _ENGINE_CTX
    if _ENGINE_CTX is None:
        cfg = smoke_config(get_config("olmo-1b"))
        params = models.init_params(cfg, jax.random.PRNGKey(0))
        _ENGINE_CTX = (cfg, params)
    return _ENGINE_CTX


@pytest.fixture(scope="module")
def engine_setup():
    return _get_engine_setup()


_STORM_ENGINES = None


def _get_storm_engines():
    """One dp=4 (mesh when available) engine + one dp=1 reference,
    reused across property examples — each example drains to idle and
    proves zero occupancy, so reuse is itself a conservation check
    (and it amortizes step compilation across the seeded examples)."""
    global _STORM_ENGINES
    if _STORM_ENGINES is None:
        cfg, params = _get_engine_setup()
        mk = lambda dp, bl: ServingEngine(
            cfg, params, dp=dp, b_local=bl, max_len=64, chunk_size=8,
            sched=SchedConfig(pin_pages=6))
        _STORM_ENGINES = (cfg, mk(4, 2), mk(1, 2))
    return _STORM_ENGINES


def _storm_requests(cfg, rng, n):
    hot = list(rng.randint(1, 255, 16))           # 2 whole pages of 8
    reqs = []
    for i in range(n):
        if rng.random() < 0.6:
            prompt = hot + list(rng.randint(1, 255, rng.randint(1, 6)))
        else:
            prompt = list(rng.randint(1, 255, rng.randint(2, 20)))
        slo = ("interactive" if rng.random() < 0.25 else
               "batch" if rng.random() < 0.3 else "standard")
        reqs.append((prompt, int(rng.randint(1, 5)), slo))
    return reqs


def _drive(eng, reqs, rid0, check=None):
    out = []
    rs = [Request(rid0 + i, prompt=list(p), max_new_tokens=mn, slo=slo)
          for i, (p, mn, slo) in enumerate(reqs)]
    # staggered submission: half up front, the rest trickling in while
    # the batch is busy (admission under pressure)
    for r in rs[:len(rs) // 2]:
        eng.submit(r)
    backlog = rs[len(rs) // 2:]
    for step in range(400):
        if backlog and step % 2 == 0:
            eng.submit(backlog.pop(0))
        if not backlog and eng.idle():
            break
        eng.step()
        if check is not None:
            check(eng)
    assert all(r.done for r in rs), "storm did not drain"
    for r in rs:
        out.append(r.out_tokens)
    eng.flush_pins()
    assert eng.page_occupancy() == 0.0, "pages leaked after drain+flush"
    return out


class TestEngineMeshStorms:
    @settings(max_examples=4, deadline=None)
    @given(seed=st.integers(0, 999))
    def test_storm_conservation_never_dry_token_identity(self, seed):
        """Admission/prefill/preempt/release storms on the dp=4 plane:
        per-shard conservation + §4.2 never-dry after every step, and
        the emitted streams are identical to the dp=1 run of the same
        trace (placement, sharing, pinning, and the mesh are all
        output-invisible)."""
        cfg, eng4, eng1 = _get_storm_engines()
        rng = np.random.RandomState(seed)
        reqs = _storm_requests(cfg, rng, 10)
        ell = pool_ell(cfg, chunk=8)
        pages_local = eng4.pages_local

        def invariants(eng):
            kv = eng.state.pool.classes[0]
            free_s = np.asarray(hier_pool.free_per_shard(kv))
            live_s = np.asarray(hier_pool.live_per_shard(kv))
            assert np.all(free_s + live_s == pages_local), (
                f"seed {seed}: per-shard conservation broken "
                f"(free={free_s.tolist()} live={live_s.tolist()})")
            tops = np.asarray(kv.private_top)
            assert tops.min() >= ell, (
                f"seed {seed}: a lane ran dry (min={tops.min()}, "
                f"ell={ell}) — §4.2 violated")

        out4 = _drive(eng4, reqs, rid0=seed * 1000, check=invariants)
        out1 = _drive(eng1, reqs, rid0=seed * 1000)
        assert out4 == out1, (
            f"seed {seed}: mesh run diverged from single-device run")

    def test_preemption_storm_on_mesh_token_identical(self, engine_setup):
        """Tight per-shard budget + interactive arrivals mid-flight:
        standard work is preempted and resumed across the mesh with
        identical output streams, and the budget ledger matches the
        device truth when the dust settles."""
        cfg, params = engine_setup
        eng = ServingEngine(cfg, params, dp=2, b_local=2, max_len=64,
                            chunk_size=8,
                            sched=SchedConfig(page_budget=6))
        rng = np.random.RandomState(7)
        std = [Request(i, prompt=list(rng.randint(1, 255, 18)),
                       max_new_tokens=6) for i in range(4)]
        for r in std:
            eng.submit(r)
        for _ in range(2):
            eng.step()
        inter = [Request(10 + i, prompt=list(rng.randint(1, 255, 10)),
                         max_new_tokens=4, slo="interactive")
                 for i in range(2)]
        for r in inter:
            eng.submit(r)
        eng.run(max_steps=400)
        assert all(r.done for r in std + inter)
        assert eng.stats["preemptions"] >= 1, "storm never preempted"
        assert eng.page_occupancy() == 0.0
        assert eng.scheduler.committed == [0] * eng.dp

        ref = ServingEngine(cfg, params, dp=1, b_local=2, max_len=64,
                            chunk_size=8)
        refs = [Request(100 + i, prompt=list(r.prompt),
                        max_new_tokens=r.max_new_tokens)
                for i, r in enumerate(std + inter)]
        for r in refs:
            ref.submit(r)
        ref.run(max_steps=400)
        assert [r.out_tokens for r in std + inter] == \
            [r.out_tokens for r in refs], "preemption changed tokens"


# ================================================== 4. mesh plumbing

class TestMeshPlumbing:
    def test_engine_builds_mesh_and_shards_state(self, engine_setup):
        cfg, params = engine_setup
        eng = ServingEngine(cfg, params, dp=2, b_local=2, max_len=48)
        if len(jax.devices()) < 2:
            assert eng.mesh is None, "mesh without enough devices"
            return
        assert eng.mesh is not None and eng.mesh.axis_names == ("dp",)
        for leaf in jax.tree.leaves(eng.state):
            s = leaf.sharding
            assert isinstance(s, NamedSharding) and "dp" in str(s.spec), (
                f"unsharded serving leaf: {leaf.shape} {s}")

    @pytest.mark.skipif(len(jax.devices()) < 4, reason="mesh-8 CI job")
    def test_one_sync_per_step_under_mesh(self, engine_setup):
        """The shard_map lift must not add device->host traffic: steady
        state is still exactly one packed-status sync per step, now
        carrying every shard's row (the all_gather ran on device)."""
        cfg, params = engine_setup
        eng = ServingEngine(cfg, params, dp=4, b_local=2, max_len=64,
                            chunk_size=8)
        assert eng.mesh is not None
        for i in range(4):
            eng.submit(Request(i, prompt=[3, 5, 7], max_new_tokens=8))
        eng.step()
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
            for _ in range(3):
                eng.step()
        finally:
            engine_mod.np = orig
        assert len(syncs) == 3, f"expected 1 sync/step, saw {syncs}"
        from repro.serving.telemetry import N_CTR
        assert all(s == (4 + N_CTR, 4, 2) for s in syncs)
        eng.run(max_steps=200)
        assert eng.page_occupancy() == 0.0
