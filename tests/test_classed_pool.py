"""Size-classed allocation plane tests (DESIGN.md §14) and the PR's
allocator accounting / sizing bugfix regressions.

* Differential conformance: a randomized MULTI-CLASS op storm (torn
  per-class rebalance windows included) replayed through the jax
  :mod:`repro.core.classed_pool` and the sequential classed witness
  (:class:`repro.core.refpool.RefClassedPool`) — identical grants,
  identical metered spills, identical final stacks per class per shard;
  the recorded history passes the class-resolved linearizability
  checkers.
* Crash/reconcile mid-storm: the classed ``audit_and_reconcile``
  rebuilds every class, the witness is re-anchored to the (deterministic)
  reconciled state, and the storm continues conformant.
* Serving token identity: a paged-only model served with
  ``size_classes=2`` emits bit-identical tokens and class-0 counters to
  the single-class engine.
* §4.2 sizing regression: a pool that passes ``create``'s
  one-batch-per-lane assert but lacks the pool-wide ``3*ell*L`` slack
  demonstrably runs a lane dry; ``validate_plan`` rejects it at plan
  time (and admits it only under ``degraded_ok``).
* Reconcile recount narrowing regression: a pathologically shared page
  (more keeping rows than int16 can count) clamps to the dtype max with
  a report entry instead of silently wrapping negative ("free").
"""

import random

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hypothesis import given, settings, strategies as st

from repro import models
from repro.configs import get_config, smoke_config
from repro.core import classed_pool, hier_pool, refpool
from repro.core.classed_pool import CLS_KV, CLS_STATE, ClassSpec
from repro.core.linearizability import (check_classed_batch_history,
                                        check_cross_class_frees,
                                        split_history_by_class)
from repro.core.sim import OpRecord
from repro.serving.engine import Request, ServingEngine
from repro.serving.telemetry import (CTR_ALLOC, CTR_FREED, N_CTR, ctr_key)

DP = 2
# three deliberately different classes: coarse (KV-like), fine
# (bounded-state), and the read-only expert-weight class (§15) — the
# storm drives all three, so every op mix, torn window, and crash/
# reconcile below exercises the expert class too
SPECS = (ClassSpec(page_size=8, num_blocks=48, num_lanes=3, ell=2),
         ClassSpec(page_size=2, num_blocks=30, num_lanes=3, ell=2),
         ClassSpec(page_size=64, num_blocks=36, num_lanes=3, ell=2))
LANES, KMAX = 3, 3


def _pad(row, k):
    return row + [-1] * (k - len(row))


class ClassedStorm:
    """Drives one randomized multi-class trace through the jax classed
    pool and the sequential witness in lockstep, asserting grant/spill
    identity per op and recording a class/shard-tagged history for the
    class-resolved linearizability checkers."""

    def __init__(self, rng, pool=None, refs=None):
        self.rng = rng
        self.pool = pool if pool is not None \
            else classed_pool.create_dp(DP, SPECS)
        self.refs = refs if refs is not None \
            else refpool.create_classed_dp(DP, SPECS)
        self.held = [[[] for _ in range(DP)] for _ in SPECS]
        self.extra = [[[] for _ in range(DP)] for _ in SPECS]
        self.torn = []                       # classes drained, not refilled
        self.history = []
        self._opid = 0
        self._step = 0

    # ---------------------------------------------------------- history
    def _rec(self, name, cls, shard, arg=None, result=None):
        self._opid += 1
        self._step += 1
        self.history.append(OpRecord(
            opid=self._opid, pid=shard, name=name, arg=arg,
            invoke_step=self._step, result=result,
            response_step=self._step,
            meta={"cls": cls, "shard": shard}))

    # ------------------------------------------------------------- ops
    def run(self, steps):
        for _ in range(steps):
            cls = self.rng.randrange(len(SPECS))
            op = self.rng.choice(["alloc", "alloc_n", "alloc_shared",
                                  "addref", "free_n", "free_n",
                                  "free_shared", "rebalance", "torn"])
            getattr(self, "_op_" + op)(cls)
            self._check_conservation()

    def _op_alloc(self, cls):
        want = np.asarray([[self.rng.random() < 0.7
                            for _ in range(LANES)] for _ in range(DP)])
        self.pool, ids = classed_pool.alloc_n_dp(
            self.pool, cls, jnp.asarray(want, jnp.int32), 1)
        got = np.asarray(ids)
        for d in range(DP):
            ref_rows = self.refs[d].alloc_n(cls, want[d].astype(int), 1)
            grants = []
            for ln in range(LANES):
                assert got[d, ln].tolist() == _pad(ref_rows[ln], 1), (
                    f"cls {cls} shard {d}: alloc diverged")
                self.held[cls][d] += ref_rows[ln]
                grants += ref_rows[ln]
            self._rec("alloc_n", cls, d, result=grants)

    def _op_alloc_n(self, cls):
        counts = np.asarray([[self.rng.randint(0, KMAX)
                              for _ in range(LANES)] for _ in range(DP)],
                            np.int32)
        self.pool, ids = classed_pool.alloc_n_dp(
            self.pool, cls, jnp.asarray(counts), KMAX)
        got = np.asarray(ids)
        for d in range(DP):
            ref_rows = self.refs[d].alloc_n(cls, counts[d], KMAX)
            grants = []
            for ln in range(LANES):
                assert got[d, ln].tolist() == _pad(ref_rows[ln], KMAX), (
                    f"cls {cls} shard {d}: alloc_n diverged")
                self.held[cls][d] += ref_rows[ln]
                grants += ref_rows[ln]
            self._rec("alloc_n", cls, d, result=grants)

    def _op_alloc_shared(self, cls):
        counts = np.asarray([[self.rng.randint(0, 2)
                              for _ in range(LANES)] for _ in range(DP)],
                            np.int32)
        self.pool, ids = classed_pool.alloc_from_shared_dp(
            self.pool, cls, jnp.asarray(counts), KMAX)
        got = np.asarray(ids)
        for d in range(DP):
            ref_rows = self.refs[d].alloc_from_shared(cls, counts[d], KMAX)
            grants = []
            for ln in range(LANES):
                assert got[d, ln].tolist() == _pad(ref_rows[ln], KMAX), (
                    f"cls {cls} shard {d}: shared alloc diverged")
                self.held[cls][d] += ref_rows[ln]
                grants += ref_rows[ln]
            self._rec("alloc_n", cls, d, result=grants)

    def _op_addref(self, cls):
        rows = []
        for d in range(DP):
            picks = ([self.rng.choice(self.held[cls][d])]
                     if self.held[cls][d] and self.rng.random() < 0.8
                     else [])
            self.extra[cls][d] += picks
            self.refs[d].addref(cls, _pad(picks, 1))
            rows.append(_pad(picks, 1))
        self.pool = classed_pool.addref_dp(
            self.pool, cls, jnp.asarray(rows, jnp.int32))

    def _op_free_n(self, cls):
        rows_dp = []
        freed = [[] for _ in range(DP)]
        for d in range(DP):
            rows = [[] for _ in range(LANES)]
            k = self.rng.randint(0, min(3, len(self.held[cls][d])))
            for _ in range(k):
                b = self.held[cls][d].pop(
                    self.rng.randrange(len(self.held[cls][d])))
                rows[self.rng.randrange(LANES)].append(b)
                freed[d].append(b)
            rows_dp.append([_pad(r, KMAX) for r in rows])
        self.pool, spilled = classed_pool.free_n_metered_dp(
            self.pool, cls, jnp.asarray(rows_dp, jnp.int32))
        sp = np.asarray(spilled)
        for d in range(DP):
            ref_spill = self.refs[d].free_n(cls, rows_dp[d])
            assert int(sp[d]) == ref_spill, (
                f"cls {cls} shard {d}: metered spill {int(sp[d])} != "
                f"witness {ref_spill}")
            self._rec("free_n", cls, d, arg=freed[d])

    def _op_free_shared(self, cls):
        rows = []
        freed = [[] for _ in range(DP)]
        for d in range(DP):
            picks = []
            if self.extra[cls][d] and self.rng.random() < 0.8:
                picks.append(self.extra[cls][d].pop())
            rows.append(_pad(picks, 1))
            freed[d] = picks
        self.pool = classed_pool.free_shared_dp(
            self.pool, cls, jnp.asarray(rows, jnp.int32))
        for d in range(DP):
            self.refs[d].free_shared(cls, rows[d])
            # extra-ref drop, not a live release: not a history "free"

    def _op_rebalance(self, cls):
        # close any torn window first (refill what was drained), then a
        # full all-class rebalance — the serve step's fused form
        if self.torn:
            c = self.torn.pop()
            self.pool = classed_pool.rebalance_refill_dp(self.pool, c)
            for d in range(DP):
                self.refs[d].rebalance_refill(c)
        self.pool = classed_pool.rebalance_dp(self.pool)
        for d in range(DP):
            self.refs[d].rebalance()

    def _op_torn(self, cls):
        # torn per-class window: drain ONE class and leave it un-refilled
        # for a while (chaos.py plants exactly this before a host crash)
        if cls in self.torn:
            return
        self.pool = classed_pool.rebalance_drain_dp(self.pool, cls)
        for d in range(DP):
            self.refs[d].rebalance_drain(cls)
        self.torn.append(cls)

    # ------------------------------------------------------ invariants
    def _check_conservation(self):
        for c, spec in enumerate(SPECS):
            free_s = np.asarray(classed_pool.free_per_shard(self.pool, c))
            live_s = np.asarray(classed_pool.live_per_shard(self.pool, c))
            for d in range(DP):
                assert free_s[d] + live_s[d] == spec.num_blocks, (
                    f"class {c} shard {d}: conservation broke")

    def check_conformance(self):
        for d in range(DP):
            msg = refpool.conforms_classed(self.refs[d], self.pool, d)
            assert msg is None, f"shard {d}: {msg}"


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 9999))
def test_classed_storm_conforms_and_linearizes(seed):
    storm = ClassedStorm(random.Random(seed))
    for _ in range(4):
        storm.run(15)
        storm.check_conformance()
    # the class-resolved checkers accept the whole tagged history
    assert check_classed_batch_history(storm.history) == []
    by_cls = split_history_by_class(storm.history)
    assert set(by_cls) <= {0, 1, 2}


def test_classed_storm_crash_reconcile_then_conforms():
    """Mid-storm crash: reconcile every class from kept page-table rows,
    re-anchor the witness to the (deterministic) reconciled state, and
    the storm continues in exact conformance."""
    rng = random.Random(7)
    storm = ClassedStorm(rng)
    storm.run(30)

    # the crash keeps a random subset of held blocks per class per shard
    keep, orphans = [], 0
    for c in range(len(SPECS)):
        width = max(1, max(len(storm.held[c][d]) for d in range(DP)))
        tab = np.full((DP, width), -1, np.int32)
        for d in range(DP):
            kept = [b for b in storm.held[c][d] if rng.random() < 0.5]
            # blocks with extra refs must be kept once per reference to
            # reproduce their refcount; keep it simple: drop extras too
            kept = [b for b in kept if b not in storm.extra[c][d]]
            dropped = [b for b in set(storm.held[c][d]) - set(kept)]
            orphans += len(set(dropped))
            tab[d, :len(kept)] = kept
            storm.held[c][d] = list(kept)
            storm.extra[c][d] = []
        keep.append(tab)

    pool, report = classed_pool.audit_and_reconcile(
        storm.pool, keep_tables=tuple(keep))
    assert report["conserved"]
    assert report["never_dry"]
    assert report["reclaimed"] >= orphans          # extras reclaim too
    assert len(report["classes"]) == len(SPECS)

    # re-anchor the witness: reconcile is deterministic (ascending free
    # ids, ell per lane, remainder reversed on the shared stack)
    sh = jax.tree.map(np.asarray, pool)
    refs = refpool.create_classed_dp(DP, SPECS)
    for d in range(DP):
        for c, rc in enumerate(refs[d].classes):
            hp = sh.classes[c]
            top = int(hp.shared.top[d])
            rc.shared = [int(x) for x in hp.shared.free_ids[d][:top]]
            rc.lanes = [
                [int(x) for x in hp.private_ids[d][i][:int(t)]]
                for i, t in enumerate(hp.private_top[d])]
            rc.refcount = [int(x) for x in hp.shared.refcount[d]]
    storm.pool, storm.refs, storm.torn = pool, refs, []
    storm.check_conformance()
    storm.run(30)
    storm.check_conformance()


def test_checker_flags_cross_class_theft():
    """A grant in class 0 freed through class 1's allocator is flagged
    by the class-resolved checkers (and invisible to a per-class-only
    split — the exact reason the cross-class pass exists)."""
    h = [
        OpRecord(opid=1, pid=0, name="alloc_n", arg=None, result=[5],
                 invoke_step=1, response_step=2,
                 meta={"cls": 0, "shard": 0}),
        OpRecord(opid=2, pid=0, name="free_n", arg=[5], result=None,
                 invoke_step=3, response_step=4,
                 meta={"cls": 1, "shard": 0}),
    ]
    errs = check_cross_class_frees(h)
    assert errs and "cross-class theft" in errs[0]
    assert check_classed_batch_history(h) != []
    # the same free in its own class is clean
    h[1].meta["cls"] = 0
    assert check_cross_class_frees(h) == []
    assert check_classed_batch_history(h) == []
    # the expert class (cls 2) is covered by the same pass: a KV grant
    # freed through CLS_EXPERT's allocator is theft too
    h[1].meta["cls"] = 2
    errs = check_cross_class_frees(h)
    assert errs and "cross-class theft" in errs[0]


# ==================================================== serving identity


@pytest.fixture(scope="module")
def engine_setup():
    cfg = smoke_config(get_config("olmo-1b"))
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _drive(eng, prompts):
    reqs = [Request(i, prompt=list(p), max_new_tokens=5)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run(max_steps=400)
    assert all(r.done for r in reqs)
    return [r.out_tokens for r in reqs]


def test_paged_only_token_identity_single_vs_two_class(engine_setup):
    """A paged-only model (state_blocks_per_slot == 0) served under
    ``size_classes=2`` is bit-identical to the single-class engine:
    same tokens, same class-0 device counters, zero class-1 traffic —
    the class axis is pure plumbing until a consumer routes to it."""
    cfg, params = engine_setup
    rng = np.random.RandomState(11)
    prompts = [list(rng.randint(1, 255, rng.randint(4, 14)))
               for _ in range(8)]

    eng1 = ServingEngine(cfg, params, dp=2, b_local=2, max_len=64,
                         prefix_sharing=False)
    out1 = _drive(eng1, prompts)
    eng2 = ServingEngine(cfg, params, dp=2, b_local=2, max_len=64,
                         prefix_sharing=False, size_classes=2)
    assert eng2.n_classes == 2
    out2 = _drive(eng2, prompts)

    assert out1 == out2, "size classes changed tokens on a paged model"
    for row in (CTR_ALLOC, CTR_FREED):
        np.testing.assert_array_equal(
            eng1.telemetry.shard[ctr_key(row, 0)],
            eng2.telemetry.shard[ctr_key(row, 0)],
            err_msg=f"class-0 counter row {row} diverged")
    # class 1 exists but nothing routed to it on a paged-only model
    assert eng2.telemetry.shard[ctr_key(CTR_ALLOC, 1)].sum() == 0
    assert int(np.asarray(
        classed_pool.live_per_shard(eng2.state.pool, CLS_STATE)).sum()) == 0
    assert eng1.page_occupancy() == 0.0 and eng2.page_occupancy() == 0.0


def test_two_class_counter_block_shape(engine_setup):
    """The packed status grows exactly one extra N_CTR block per class
    and the telemetry facade accounts both classes."""
    cfg, params = engine_setup
    eng = ServingEngine(cfg, params, dp=1, b_local=2, max_len=64,
                        size_classes=2)
    rng = np.random.RandomState(12)
    eng.submit(Request(0, prompt=list(rng.randint(1, 255, 6)),
                       max_new_tokens=4))
    eng.run(max_steps=100)
    assert eng.telemetry.n_classes == 2
    assert eng.telemetry.last_block.shape == (2 * N_CTR, 1)
    assert ctr_key(CTR_ALLOC, 1) == "alloc_pages_c1"
    assert ctr_key(CTR_ALLOC, 0) == "alloc_pages"


# ========================================== §4.2 plan validation (bugfix)


def test_validate_plan_catches_tight_config():
    """num_blocks = 6, lanes = 2, ell = 2 passes ``create``'s
    one-batch-per-lane assert, yet with max_live = 4 the §4.2 slack
    3*ell*L = 12 is unavailable — a lane demonstrably runs dry between
    rebalances.  ``validate_plan`` rejects the plan; ``degraded_ok``
    admits it flagged."""
    with pytest.raises(ValueError, match="run dry"):
        hier_pool.validate_plan(6, 2, 2, max_live=4)
    assert hier_pool.validate_plan(6, 2, 2, max_live=4,
                                   degraded_ok=True) is False
    assert hier_pool.validate_plan(4 + 12, 2, 2, max_live=4) is True

    # the dry lane is real, not theoretical: drain lane 0 twice with
    # max_live=4 held and the refill has nothing to grant
    pool = hier_pool.create(6, 2, 2)          # passes create's assert
    pool, ids = hier_pool.alloc_n(pool, jnp.asarray([2, 0], jnp.int32), 2)
    assert (np.asarray(ids)[0] >= 0).all()
    pool = hier_pool.rebalance(pool)          # refills lane 0 from shared
    pool, ids = hier_pool.alloc_n(pool, jnp.asarray([2, 0], jnp.int32), 2)
    assert (np.asarray(ids)[0] >= 0).all()    # max_live = 4 reached
    pool = hier_pool.rebalance(pool)          # shared is empty: no refill
    tops = np.asarray(pool.private_top)
    ell = hier_pool.lane_ell(pool)
    assert tops[0] < ell, "lane should have run dry (the §4.2 violation)"
    pool, ids = hier_pool.alloc_n(pool, jnp.asarray([1, 0], jnp.int32), 1)
    assert int(np.asarray(ids)[0, 0]) == -1, (
        "dry lane granted — expected a NULL grant on the hot path")
    # free blocks exist (lane 1 holds 2): the failure is distribution,
    # exactly what the plan-time slack requirement prevents
    assert int(hier_pool.total_free(pool)) > 0


def test_classed_validate_specs_names_failing_class():
    ok = classed_pool.validate_specs(
        SPECS, max_live=[30, 12, 16], degraded_ok=False)
    assert ok == (True, True, True)
    with pytest.raises(ValueError, match="class 1"):
        classed_pool.validate_specs(SPECS, max_live=[30, 29, 16])
    flags = classed_pool.validate_specs(SPECS, max_live=[30, 29, 16],
                                        degraded_ok=True)
    assert flags == (True, False, True)


def test_engine_validates_pool_plan(engine_setup):
    """The serving engine runs the §4.2 plan validation over its whole
    class vector at construction and records full provisioning; the
    sizing rule (`pool_class_specs`) always passes it by construction,
    so the check is the guard rail for future sizing changes."""
    cfg, params = engine_setup
    eng = ServingEngine(cfg, params, dp=1, b_local=2, max_len=64)
    assert eng.pool_provisioned == (True,) * eng.n_classes
    eng2 = ServingEngine(cfg, params, dp=1, b_local=2, max_len=64,
                         size_classes=2, degraded_pool_ok=True)
    assert eng2.pool_provisioned == (True,) * eng2.n_classes


# ==================================== reconcile int16 narrowing (bugfix)


def test_reconcile_clamps_pathological_refcount():
    """> int16-max keeping rows reference one page: the int64 recount
    must clamp at the dtype max (page stays live, reported) instead of
    silently wrapping negative on the narrow (page turns 'free' and
    gets double-granted)."""
    pool = hier_pool.create(8, 2, 2)
    cap = np.iinfo(np.asarray(pool.shared.refcount).dtype).max
    rows = np.zeros((cap + 5, 1), np.int32)        # all reference block 0
    new_pool, report = hier_pool.audit_and_reconcile(pool, keep_tables=rows)
    assert report["conserved"]
    assert report["clamped"] == 1
    assert report["shards"][0]["clamped"] == [0]
    rc = np.asarray(new_pool.shared.refcount)
    assert rc[0] == cap, "clamp must pin to the dtype max"
    assert rc[0] > 0, "the pathologically shared page must stay live"
    # conservation: block 0 live, the other 7 free
    assert int(hier_pool.num_live(new_pool)) == 1
    assert int(hier_pool.total_free(new_pool)) == 7

    # classed merge surfaces the clamp count too
    cpool = classed_pool.create(
        (ClassSpec(8, 8, 2, 2), ClassSpec(2, 8, 2, 2)))
    _, rep = classed_pool.audit_and_reconcile(
        cpool, keep_tables=(rows, None))
    assert rep["clamped"] == 1
    assert rep["classes"][0]["clamped"] == 1
    assert rep["classes"][1]["clamped"] == 0
