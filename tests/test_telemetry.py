"""Observability plane tests (DESIGN.md §13).

The device counter block is checked EXACTLY against an independent
host-side recount: a harness wraps the engine's jitted-step dispatch to
snapshot the feed (lane widths, prompt flags, the ``_fed`` shadow) and
wraps the flight recorder to pair each completed step's packed status
with that snapshot, then recomputes what each counter row must be from
page arithmetic alone — under plain storms, preemption, speculative
rollback, and a torn drain/refill crash window.  The tracer's chrome
export is schema-validated with strict span nesting, and the one-sync /
one-collective discipline is re-asserted with telemetry fully enabled.
"""

import gc
import json
import os
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import models
from repro.configs import get_config, smoke_config
from repro.serving import chaos
from repro.serving.engine import Request, ServingEngine, step_collectives
from repro.serving.telemetry import (CTR_ALLOC, CTR_DRAIN, CTR_FREED,
                                     CTR_MARGIN, CTR_REFILL, CTR_ROLLBACK,
                                     CTR_SHARED_FREE, CTR_SPILL, N_CTR,
                                     FlightRecorder, Telemetry, parse_prom)
from repro.serving import trace as trace_mod
from repro.serving.trace import (DEVICE_SCOPES, PHASE_NAMES, Tracer,
                                 validate_chrome)


@pytest.fixture(scope="module")
def engine_setup():
    cfg = smoke_config(get_config("olmo-1b"))
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# ===================================================== host-side recount
#
# Independent replay of the counter block from host state.  At dispatch
# the harness snapshots feed_lens/is_prompt and the _fed shadow (prompt
# slots are already advanced at dispatch, generating slots are not);
# when the engine records the step into the flight ring the harness
# reads the packed status and recomputes, per shard, from ceil-division
# page arithmetic alone:
#
#   alloc    = sum_slots  pages(fed_before + fed) - pages(fed_before)
#   rollback = sum_gen    pages(fed_before + fed) - pages(fed_before+ne)
#   freed    = rollback + sum_done pages(final_kept_tokens)
#
# Exact only when every page has refcount 1 — so these storms run with
# prefix sharing off (or with no overlapping same-prefix residency and
# the default pin budget of zero, which never creates a pin).


class Recount:
    def __init__(self, eng):
        self.eng = eng
        self.psz = eng.cfg.page_size
        self.expected = []          # one dict per completed step
        self.observed = []          # matching int ctr blocks [N_CTR, DP]
        self.margins = []           # device-read min(private_top)-ell
        self.preempt_freed = 0      # pages released outside the step
        self.host_freed = []        # per step: host-side free since prev
        self._host_freed = False
        self._pending = None
        self._post_shared = None
        self._wrap_variants()
        self._wrap_flight()
        self._wrap_preempt()

    def _wrap_variants(self):
        eng = self.eng
        for key, fn in list(eng._serve_variants.items()):
            eng._serve_variants[key] = self._make_wrapper(fn)

    def _make_wrapper(self, fn):
        eng = self.eng

        def wrapped(params, state, last_tok, out_count, budget, temps,
                    topks, seeds, prompt_toks, feed_lens, is_prompt, emit,
                    expert_mask):
            self._pending = {
                "feed": np.asarray(feed_lens).copy(),
                "is_prompt": np.asarray(is_prompt).copy(),
                "fed": dict(eng._fed),
            }
            return fn(params, state, last_tok, out_count, budget, temps,
                      topks, seeds, prompt_toks, feed_lens, is_prompt,
                      emit, expert_mask)
        return wrapped

    def _wrap_flight(self):
        flight = self.eng.flight
        orig = flight.record

        def record(**rec):
            self._on_step(rec)
            orig(**rec)
        flight.record = record

    def _wrap_preempt(self):
        eng = self.eng
        orig = eng.preempt

        def preempt(slot):
            # refcount-1 release outside the step's counter block
            self.preempt_freed += -(-eng._fed.get(slot, 0) // self.psz)
            self._host_freed = True
            return orig(slot)
        eng.preempt = preempt

    def _on_step(self, rec):
        eng, psz = self.eng, self.psz
        snap, self._pending = self._pending, None
        assert snap is not None, "flight.record without a dispatch"
        status = np.asarray(rec["status"])
        T = rec["T"]
        n_emit = status[T + 0]
        done = status[T + 1]
        ctr = status[T + 3:, :, 0]
        assert ctr.shape == (N_CTR, eng.dp)

        pages = lambda x: -(-x // psz)               # noqa: E731
        alloc = np.zeros(eng.dp, np.int64)
        roll = np.zeros(eng.dp, np.int64)
        freed = np.zeros(eng.dp, np.int64)
        for d in range(eng.dp):
            for b in range(eng.bl):
                fed = int(snap["feed"][d, b])
                if fed == 0:
                    continue
                slot = d * eng.bl + b
                if snap["is_prompt"][d, b]:
                    # _fed advanced at dispatch: before = after - fed
                    before = snap["fed"].get(slot, 0) - fed
                    kept = before + fed
                else:
                    before = snap["fed"].get(slot, 0)
                    ne = int(n_emit[d, b])
                    kept = before + ne
                    roll[d] += pages(before + fed) - pages(kept)
                alloc[d] += pages(before + fed) - pages(before)
                if done[d, b]:
                    freed[d] += pages(kept)
        freed += roll
        self.expected.append({"alloc": alloc, "roll": roll,
                              "freed": freed})
        self.observed.append(ctr.astype(np.int64))
        self.host_freed.append(self._host_freed)
        self._host_freed = False
        # device-read invariant gauges (test-only sync): the §4.2
        # margin and shared level the block must have reported (the
        # KV class — these storms run single-class engines)
        kv = eng.state.pool.classes[0]
        ell = kv.private_ids.shape[-1] // 3
        self.margins.append(
            np.asarray(jnp.min(kv.private_top, axis=-1)) - ell)
        self._post_shared = np.asarray(kv.shared.top).copy()

    def check(self):
        assert self.expected, "no steps recorded"
        ell = self.eng.state.pool.classes[0].private_ids.shape[-1] // 3
        for i, (exp, obs) in enumerate(zip(self.expected, self.observed)):
            np.testing.assert_array_equal(
                obs[CTR_ALLOC], exp["alloc"],
                err_msg=f"step {i}: alloc recount mismatch")
            np.testing.assert_array_equal(
                obs[CTR_ROLLBACK], exp["roll"],
                err_msg=f"step {i}: rollback recount mismatch")
            np.testing.assert_array_equal(
                obs[CTR_FREED], exp["freed"],
                err_msg=f"step {i}: freed recount mismatch")
            # §4.2 never-dry margin: non-negative at every step, and
            # exactly the device state the step left behind
            assert (obs[CTR_MARGIN] >= 0).all(), \
                f"step {i}: never-dry margin went negative"
            np.testing.assert_array_equal(
                obs[CTR_MARGIN], self.margins[i],
                err_msg=f"step {i}: margin gauge mismatch")
            # drain/refill move whole batches of ell per lane
            assert (obs[CTR_DRAIN] % ell == 0).all()
            assert (obs[CTR_REFILL] % ell == 0).all()
        # The shared free level moves by +drain -refill +spill each
        # step — the lane-cap overflow of every IN-STEP release is now
        # metered in CTR_SPILL, so the telescoping is an exact identity,
        # not the old drain/refill-only floor inequality.  Host-side
        # frees between steps (preempt's separate jitted release) spill
        # to shared OUTSIDE any counter block, so on steps following one
        # the identity relaxes back to a (tighter-than-before) floor.
        for i in range(1, len(self.observed)):
            prev, obs = self.observed[i - 1], self.observed[i]
            floor = (prev[CTR_SHARED_FREE] + obs[CTR_DRAIN]
                     - obs[CTR_REFILL] + obs[CTR_SPILL])
            if self.host_freed[i]:
                assert (obs[CTR_SHARED_FREE] >= floor).all(), \
                    f"step {i}: shared-free fell below the spill floor"
            else:
                np.testing.assert_array_equal(
                    obs[CTR_SHARED_FREE], floor,
                    err_msg=f"step {i}: shared-free telescoping is not "
                            f"exact (drain/refill/spill)")
        np.testing.assert_array_equal(
            self.observed[-1][CTR_SHARED_FREE], self._post_shared,
            err_msg="final shared-free gauge disagrees with device state")
        # host-side telemetry accumulated the same totals
        tel = self.eng.telemetry
        np.testing.assert_array_equal(
            tel.shard["alloc_pages"],
            sum(e["alloc"] for e in self.expected))
        np.testing.assert_array_equal(
            tel.shard["freed_pages"],
            sum(e["freed"] for e in self.expected))
        assert tel.never_dry_margin_min() is not None
        assert tel.never_dry_margin_min() >= 0


def _alloc_freed_balance(rc):
    total_alloc = int(sum(e["alloc"] for e in rc.expected).sum())
    total_freed = int(sum(e["freed"] for e in rc.expected).sum())
    assert total_alloc == total_freed + rc.preempt_freed, (
        f"page conservation broke: alloc={total_alloc} "
        f"freed={total_freed} preempt_freed={rc.preempt_freed}")


def test_counter_block_exact_on_storm(engine_setup):
    """Seeded storm: every counter row matches the host recount, step
    by step, and the invariant gauges match device state exactly."""
    cfg, params = engine_setup
    rng = np.random.RandomState(0)
    eng = ServingEngine(cfg, params, dp=2, b_local=2, max_len=64,
                        prefix_sharing=False)
    rc = Recount(eng)
    reqs = [Request(i, prompt=list(rng.randint(1, 255, rng.randint(3, 14))),
                    max_new_tokens=5) for i in range(10)]
    for r in reqs:
        eng.submit(r)
    eng.run(max_steps=500)
    assert all(r.done for r in reqs)
    rc.check()
    _alloc_freed_balance(rc)
    assert eng.page_occupancy() == 0.0


def test_counter_block_exact_under_forced_spill(engine_setup):
    """Spill-forcing trace: a request that completes holding more pages
    than a whole lane can hold (> 3*ell) MUST overflow the lane cap at
    its in-step release — CTR_SPILL meters the overflow and the shared-
    free telescoping stays an exact identity through it (the bug the
    spill row fixes: unmetered spill made the gauge drift off the
    drain/refill ledger)."""
    cfg, params = engine_setup
    rng = np.random.RandomState(8)
    eng = ServingEngine(cfg, params, dp=1, b_local=2, max_len=64,
                        prefix_sharing=False)
    kv = eng.state.pool.classes[0]
    ell = kv.private_ids.shape[-1] // 3
    cap_tokens = 3 * ell * cfg.page_size
    rc = Recount(eng)
    # each request retires > 3*ell pages in one release: guaranteed
    # lane-cap overflow no matter what the lane held beforehand
    reqs = [Request(i, prompt=list(rng.randint(1, 255, cap_tokens + 2)),
                    max_new_tokens=3) for i in range(4)]
    for r in reqs:
        eng.submit(r)
    eng.run(max_steps=500)
    assert all(r.done for r in reqs)
    rc.check()
    _alloc_freed_balance(rc)
    spilled = int(sum(obs[CTR_SPILL].sum() for obs in rc.observed))
    assert spilled > 0, "trace never forced a lane-cap spill"
    np.testing.assert_array_equal(
        eng.telemetry.shard["spill_pages"],
        sum(obs[CTR_SPILL] for obs in rc.observed))
    assert eng.page_occupancy() == 0.0


def test_counter_block_exact_under_preemption(engine_setup):
    """Interactive-class arrivals force preemptions mid-storm; the
    counter block stays exact (preempt-path frees happen in a separate
    jitted release call, accounted host-side by the harness)."""
    cfg, params = engine_setup
    rng = np.random.RandomState(1)
    eng = ServingEngine(cfg, params, dp=2, b_local=2, max_len=64,
                        prefix_sharing=False)
    rc = Recount(eng)
    batch = [Request(i, prompt=list(rng.randint(1, 255, 12)),
                     max_new_tokens=8, slo="batch") for i in range(4)]
    for r in batch:
        eng.submit(r)
    for _ in range(3):
        eng.step()
    hot = [Request(100 + i, prompt=list(rng.randint(1, 255, 10)),
                   max_new_tokens=4, slo="interactive") for i in range(4)]
    for r in hot:
        eng.submit(r)
    eng.run(max_steps=500)
    assert all(r.done for r in batch + hot)
    assert eng.stats["preemptions"] > 0, "storm never preempted"
    assert rc.preempt_freed > 0
    rc.check()
    _alloc_freed_balance(rc)
    assert eng.page_occupancy() == 0.0


def test_counter_block_exact_under_spec_rollback(engine_setup):
    """Speculative repeats with a poisoned draft history: rejected-draft
    whole-page rollback shows up in CTR_ROLLBACK exactly, and the device
    total equals the host-model ``spec_pages_rolled_back`` counter."""
    cfg, params = engine_setup
    rng = np.random.RandomState(2)
    # draft_len=7: a fully-rejected draft over-allocates a whole page at
    # the prompt-length alignment below, so rollback is provably > 0
    eng = ServingEngine(cfg, params, dp=1, b_local=2, max_len=64,
                        speculate=True, draft_len=7, spec_gate=False)
    assert eng.spec_store is not None
    psz = cfg.page_size
    rc = Recount(eng)
    prompt = list(rng.randint(1, 255, psz + 6))   # key = first whole page
    first = Request(0, prompt=list(prompt), max_new_tokens=8)
    eng.submit(first)
    eng.run(max_steps=200)
    assert first.done
    key = eng.spec_store.key_of(prompt)
    assert key is not None
    # sequential repeats (never co-resident, pin budget 0 → every page
    # stays refcount-1); poisoning the recorded continuation before each
    # forces a full-draft rejection on the repeat's first spec step
    # the drafting suffix includes the first generated token, so the
    # poisoned stream must match through it and diverge right after —
    # the repeat then drafts a full-width garbage lane and rejects it
    garbage = (int(first.out_tokens[0]),) \
        + tuple(int(t) + 1 for t in first.out_tokens[1:7]) + (3,) * 7
    for i in range(1, 4):
        # the store keeps several streams per key and drafts from the
        # first consistent one — drop the true history recorded at the
        # previous finish so only the poisoned stream can draft
        eng.spec_store.streams.pop(key, None)
        eng.spec_store.record(key, tuple(prompt[len(key):]) + garbage)
        rep = Request(i, prompt=list(prompt), max_new_tokens=8)
        eng.submit(rep)
        eng.run(max_steps=300)
        assert rep.done
        assert rep.out_tokens == first.out_tokens   # rollback is exact
    assert eng.stats["spec_lanes"] > 0, "no speculative lanes dispatched"
    rc.check()
    dev_roll = int(sum(e["roll"] for e in rc.expected).sum())
    assert dev_roll > 0, "poisoned drafts never rolled a page back"
    assert dev_roll == eng.stats["spec_pages_rolled_back"], (
        "device rollback row disagrees with the host rollback model")
    _alloc_freed_balance(rc)
    assert eng.page_occupancy() == 0.0


# ================================================= acceptance criterion:
# torn-window chaos run -> flight dump == host replay


def test_flight_dump_matches_host_replay_through_torn_crash(
        engine_setup, tmp_path):
    """A seeded chaos run (host crash inside the torn drain/refill
    window) leaves a flight-recorder dump whose recorded never-dry
    margins and per-shard alloc/free counter rows exactly match the
    harness's host-side replay of the same steps."""
    cfg, params = engine_setup
    rng = np.random.RandomState(3)
    fpath = str(tmp_path / "flight.json")
    journal = chaos.ServingJournal()
    injector = chaos.parse_faults("crash@4:post_sync:torn")
    recounts = []

    def build():
        eng = ServingEngine(
            cfg, params, dp=2, b_local=2, max_len=64,
            prefix_sharing=False, journal=journal, injector=injector,
            flight=FlightRecorder(capacity=64, path=fpath))
        recounts.append(Recount(eng))
        return eng

    eng = build()
    reqs = [Request(i, prompt=list(rng.randint(1, 255, 10)),
                    max_new_tokens=5) for i in range(6)]
    for r in reqs:
        eng.submit(r)
    crashes = 0
    for _ in range(200):
        if eng.idle():
            break
        try:
            eng.run(max_steps=1)
        except chaos.HostCrash:
            crashes += 1
            eng, report = chaos.recover_engine(build, eng, journal)
    assert eng.idle(), "run never drained"
    assert crashes == 1, "the torn-window crash never fired"
    assert not journal.in_flight()
    for rc in recounts:
        rc.check()

    # the crash-time dump (overwritten by recover_engine's) holds the
    # pre-crash window — the crashed dispatch itself never reached the
    # ring, exactly like the harness's pending-discard
    mid = FlightRecorder.load(fpath)
    assert mid["reason"] == "recover_engine"
    assert len(mid["records"]) == len(recounts[0].expected)

    # final dump: the adopted ring holds BOTH engines' steps in order —
    # pair them with the harness's per-step host replay, in order
    eng.flight.dump("test_final")
    dump = FlightRecorder.load(fpath)
    records = dump["records"]
    expected = [e for rc in recounts for e in rc.expected]
    margins = [m for rc in recounts for m in rc.margins]
    assert len(records) == len(expected)
    for i, (rec, exp) in enumerate(zip(records, expected)):
        ctr = np.asarray(rec["ctr"], np.int64)
        np.testing.assert_array_equal(
            ctr[CTR_ALLOC], exp["alloc"],
            err_msg=f"dump record {i}: alloc vs host replay")
        np.testing.assert_array_equal(
            ctr[CTR_FREED], exp["freed"],
            err_msg=f"dump record {i}: freed vs host replay")
        np.testing.assert_array_equal(
            ctr[CTR_MARGIN], margins[i],
            err_msg=f"dump record {i}: margin vs host replay")
        assert (ctr[CTR_MARGIN] >= 0).all()


# ====================================================== tracer / chrome


def test_chrome_trace_schema_and_nesting(engine_setup, tmp_path):
    cfg, params = engine_setup
    rng = np.random.RandomState(4)
    eng = ServingEngine(cfg, params, dp=2, b_local=2, max_len=64,
                        tracer=Tracer())
    reqs = [Request(i, prompt=list(rng.randint(1, 255, 8)),
                    max_new_tokens=4,
                    slo="interactive" if i % 3 == 0 else "standard")
            for i in range(6)]
    for r in reqs:
        eng.submit(r)
    eng.run(max_steps=300)
    assert all(r.done for r in reqs)

    doc = eng.tracer.to_chrome()
    validate_chrome(doc)                   # schema + strict B/E nesting
    names = {ev["name"] for ev in doc["traceEvents"]}
    for must in ("request", "active", "submit", "admit", "prefill_chunk",
                 "first_token", "finish"):
        assert must in names, f"span taxonomy missing {must!r}"
    # every request's lifecycle ordering holds on its own trace row
    for r in reqs:
        kinds = [e["name"] for e in doc["traceEvents"]
                 if e["tid"] == r.rid]
        assert kinds.index("submit") < kinds.index("admit") \
            < kinds.index("first_token") < kinds.index("finish")
    # file exports round-trip
    p = eng.tracer.write_chrome(str(tmp_path / "trace.json"))
    with open(p) as fh:
        validate_chrome(json.load(fh))
    pj = eng.tracer.write_jsonl(str(tmp_path / "trace.jsonl"))
    with open(pj) as fh:
        lines = [json.loads(ln) for ln in fh]
    assert len(lines) == len(doc["traceEvents"])


def test_trace_preemption_reopens_active_span(engine_setup):
    """Preempt closes the 'active' span, readmission reopens it —
    nesting stays valid across preemption cycles."""
    cfg, params = engine_setup
    rng = np.random.RandomState(5)
    eng = ServingEngine(cfg, params, dp=1, b_local=2, max_len=64,
                        tracer=Tracer())
    batch = [Request(i, prompt=list(rng.randint(1, 255, 10)),
                     max_new_tokens=8, slo="batch") for i in range(2)]
    for r in batch:
        eng.submit(r)
    for _ in range(2):
        eng.step()
    hot = [Request(10 + i, prompt=list(rng.randint(1, 255, 8)),
                   max_new_tokens=3, slo="interactive") for i in range(2)]
    for r in hot:
        eng.submit(r)
    eng.run(max_steps=300)
    assert eng.stats["preemptions"] > 0
    doc = eng.tracer.to_chrome()
    validate_chrome(doc)
    preempted = {e["tid"] for e in doc["traceEvents"]
                 if e["name"] == "preempt"}
    assert preempted, "no preempt instants traced"
    for tid in preempted:
        actives = [e for e in doc["traceEvents"]
                   if e["tid"] == tid and e["name"] == "active"]
        assert len(actives) >= 4, (
            "preempted request should close and reopen its active span")


# =============================================== phases / device scopes


STEP_PHASES = ["serve.admit", "serve.feed", "serve.dispatch", "serve.sync",
               "serve.post", "serve.flight"]


@pytest.fixture
def phase_sink(monkeypatch):
    """Stands in for ``jax.profiler.TraceAnnotation``: records
    ``(depth, name)`` on entry, no profiler involved (garbage
    collections, ``serve.gc``, are left out)."""
    got, depth = [], [0]

    class Sink:
        def __init__(self, name, **kwargs):
            self.name, self.kwargs = name, kwargs

        def __enter__(self):
            if self.name != "serve.gc":
                got.append((depth[0], self.name))
                depth[0] += 1
            return self

        def __exit__(self, *exc):
            if self.name != "serve.gc":
                depth[0] -= 1

    monkeypatch.setattr(trace_mod, "TraceAnnotation", Sink)
    return got


def test_dispatching_step_nests_its_phases_in_order(engine_setup,
                                                    phase_sink):
    cfg, params = engine_setup
    eng = ServingEngine(cfg, params, dp=1, b_local=2, max_len=64,
                        tracer=Tracer())
    eng.submit(Request(0, prompt=list(range(1, 9)), max_new_tokens=4))
    assert eng.step()
    assert phase_sink == [(0, "serve.step")] + [(1, n) for n in STEP_PHASES]
    # the ring keeps the request lifecycle only
    assert not {e["name"] for e in eng.tracer.events} & PHASE_NAMES


def test_idle_step_emits_step_and_admit_only(engine_setup, phase_sink):
    cfg, params = engine_setup
    eng = ServingEngine(cfg, params, dp=1, b_local=2, max_len=64,
                        tracer=Tracer())
    assert not eng.step()
    assert phase_sink == [(0, "serve.step"), (1, "serve.admit")]


def test_disabled_tracer_writes_no_phase(engine_setup, phase_sink):
    cfg, params = engine_setup
    eng = ServingEngine(cfg, params, dp=1, b_local=2, max_len=64)
    eng.submit(Request(0, prompt=list(range(1, 9)), max_new_tokens=2))
    assert eng.step()
    assert phase_sink == []


def test_gc_span_while_an_enabled_tracer_lives(monkeypatch):
    spans = []

    class Sink:
        def __init__(self, name, **kwargs):
            spans.append((name, kwargs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            pass

    monkeypatch.setattr(trace_mod, "TraceAnnotation", Sink)
    gc.collect()
    users = trace_mod._gc_users
    tracer = Tracer()
    Tracer(enabled=False)
    assert trace_mod._gc_users == users + 1
    assert trace_mod._on_gc in gc.callbacks
    gc.collect()
    assert ("serve.gc", {"generation": 2}) in spans
    del tracer
    gc.collect()
    assert trace_mod._gc_users == users
    assert (trace_mod._on_gc in gc.callbacks) == bool(users)


def test_phase_and_scope_names_are_closed():
    with pytest.raises(AssertionError):
        Tracer().phase("serve.nope")
    with pytest.raises(AssertionError):
        trace_mod.scope("nope")
    assert all(n.startswith("serve.") for n in PHASE_NAMES)
    assert "step" not in trace_mod.SPAN_NAMES


def test_serve_step_hlo_names_its_scopes(engine_setup):
    """The smoke serve step's HLO is module ``jit_serve_step`` and its
    ops carry the device scopes in their ``op_name`` metadata."""
    from jax._src.lib import xla_client
    cfg, params = engine_setup
    eng = ServingEngine(cfg, params, dp=1, b_local=2, max_len=64)
    T = eng.chunk
    feed = (jax.ShapeDtypeStruct((1, 2, T), jnp.int32),
            jax.ShapeDtypeStruct((1, 2), jnp.int32),
            jax.ShapeDtypeStruct((1, 2), bool),
            jax.ShapeDtypeStruct((1, 2), bool))
    module = eng._serve_variants[(False, False)].lower(
        eng.params, eng.state, eng.last_tok, eng.out_count, eng.budget,
        eng.temps, eng.topks, eng.seeds, *feed,
        eng.expert_mask).compiler_ir("hlo").as_hlo_module()
    assert module.name == "jit_serve_step"
    opts = xla_client._xla.HloPrintOptions.short_parsable()
    opts.print_metadata = True
    parts = set()
    for name in re.findall(r'op_name="([^"]*)"', module.to_string(opts)):
        parts.update(name.split("/"))
    assert DEVICE_SCOPES <= parts


# ============================================ one sync / one collective


def test_one_sync_per_step_with_telemetry_enabled(engine_setup, tmp_path):
    """Telemetry fully on — tracer, flight recorder with a path and
    periodic sync, device counters — the step still performs exactly
    ONE device->host sync."""
    cfg, params = engine_setup
    rng = np.random.RandomState(6)
    fpath = str(tmp_path / "fl.json")
    eng = ServingEngine(
        cfg, params, dp=1, b_local=2, max_len=64, tracer=Tracer(),
        flight=FlightRecorder(capacity=16, path=fpath, sync_every=2))
    for i in range(4):
        eng.submit(Request(i, prompt=list(rng.randint(1, 255, 6)),
                           max_new_tokens=8))
    eng.step()                             # admission + prefill chunk

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
    assert all(s == syncs[0] for s in syncs), syncs
    assert syncs[0][0] >= 1 + 3 + N_CTR and syncs[0][1:] == (1, 2), syncs
    assert os.path.exists(fpath), "periodic flight sync never wrote"
    assert FlightRecorder.load(fpath)["records"]


@pytest.mark.skipif(len(jax.devices()) < 4, reason="mesh-8 CI job")
def test_one_collective_per_step_with_telemetry(engine_setup):
    """dp=4 shard_map plane: the default serve variant compiles exactly
    one collective (the status all_gather) with the counter block
    riding the status rows."""
    cfg, params = engine_setup
    eng = ServingEngine(cfg, params, dp=4, b_local=2, max_len=64)
    assert eng.mesh is not None
    n_gather, n_other = step_collectives(
        eng.compiled_step_text(eng.chunk, spec=False))
    assert n_gather == 1, f"expected exactly one all_gather: {n_gather}"
    assert n_other == 0, f"unexpected extra collectives: {n_other}"


# ======================================================= facade / prom


def test_stats_property_backward_compatible(engine_setup):
    cfg, params = engine_setup
    eng = ServingEngine(cfg, params, dp=1, b_local=2, max_len=48)
    assert eng.stats is eng.telemetry.counters       # one live ledger
    eng.stats["deadline_expired"] += 1               # external dict write
    assert eng.telemetry.counters["deadline_expired"] == 1
    eng.telemetry.inc("deadline_expired")
    assert eng.stats["deadline_expired"] == 2
    with pytest.raises(KeyError):
        eng.telemetry.inc("not_a_counter")
    with pytest.raises(KeyError):
        eng.telemetry.observe_hist("not_a_hist", 1)


def test_prom_render_parse_roundtrip():
    tel = Telemetry(dp=2)
    tel.inc("tokens_out", 42)
    tel.inc("sched_deferred", 3)
    tel.set_max("pages_peak", 17)
    tel.observe_hist("chunk_hist", 8, 5)
    blk = np.zeros((N_CTR, 2), np.int32)
    blk[CTR_ALLOC] = [4, 6]
    blk[CTR_FREED] = [1, 2]
    blk[CTR_SHARED_FREE] = [30, 20]
    blk[CTR_MARGIN] = [5, 3]
    tel.absorb_counter_block(blk)
    blk2 = blk.copy()
    blk2[CTR_SHARED_FREE] = [25, 26]
    blk2[CTR_MARGIN] = [7, 2]
    tel.absorb_counter_block(blk2)

    metrics = parse_prom(tel.render_prom())
    assert metrics["repro_tokens_out"][()] == 42
    assert metrics["repro_sched_deferred"][()] == 3
    assert metrics["repro_pages_peak"][()] == 17
    assert metrics["repro_chunk_hist"][(("bucket", "8"),)] == 5
    assert metrics["repro_alloc_pages"][(("shard", "0"),)] == 8
    assert metrics["repro_alloc_pages"][(("shard", "1"),)] == 12
    # gauges min-accumulate per shard
    assert metrics["repro_shared_free_min"][(("shard", "0"),)] == 25
    assert metrics["repro_shared_free_min"][(("shard", "1"),)] == 20
    assert metrics["repro_never_dry_margin_min"][(("shard", "1"),)] == 2
    assert metrics["repro_never_dry_margin_min_all"][()] == 2
    assert tel.never_dry_margin_min() == 2
    snap = tel.snapshot()
    assert snap["never_dry_margin_min"] == 2
    assert snap["per_shard"]["alloc_pages"] == [8, 12]
    json.dumps(snap)                        # bench-embeddable


def test_flight_recorder_ring_and_atomic_dump(tmp_path):
    p = str(tmp_path / "ring.json")
    fl = FlightRecorder(capacity=4, path=p)
    for i in range(10):
        fl.record(step=i, payload=np.arange(3, dtype=np.int32))
    assert len(fl.ring) == 4                # bounded
    out = fl.dump("unit_test", {"note": 1})
    assert out == p
    got = FlightRecorder.load(p)
    assert got["reason"] == "unit_test"
    assert got["extra"] == {"note": 1}
    assert [r["step"] for r in got["records"]] == [6, 7, 8, 9]
    assert got["records"][0]["payload"] == [0, 1, 2]   # np -> json
    assert not [f for f in os.listdir(tmp_path)
                if f.startswith("ring.json.") and f != "ring.json"], \
        "torn temp file left behind"
    # adoption carries the window into a successor recorder
    fl2 = FlightRecorder(capacity=8)
    fl2.adopt(fl)
    assert [r["step"] for r in fl2.ring] == [6, 7, 8, 9]
    assert fl2.path == p


def test_reconcile_report_traced_and_dumped(engine_setup, tmp_path):
    """In-place recovery emits the structured reconcile report through
    the tracer and dumps the flight ring with the report attached."""
    cfg, params = engine_setup
    rng = np.random.RandomState(7)
    fpath = str(tmp_path / "fl.json")
    eng = ServingEngine(cfg, params, dp=1, b_local=2, max_len=64,
                        tracer=Tracer(),
                        flight=FlightRecorder(capacity=8, path=fpath))
    eng.submit(Request(0, prompt=list(rng.randint(1, 255, 8)),
                       max_new_tokens=6))
    for _ in range(3):
        eng.step()
    report = eng._recover_inplace()
    assert report["conserved"]
    evs = list(eng.tracer.events)
    rec = [e for e in evs if e["name"] == "reconcile"]
    assert rec, "reconcile never traced"
    assert rec[0]["args"]["conserved"]
    assert any(e["name"] == "recover" and e["ph"] == "B" for e in evs)
    assert any(e["name"] == "recover" and e["ph"] == "E" for e in evs)
    dump = FlightRecorder.load(fpath)
    assert dump["reason"] == "audit_and_reconcile"
    assert dump["extra"]["report"]["conserved"]
    assert eng.stats["flight_dumps"] >= 1
    # the requeued request still completes after recovery
    eng.run(max_steps=300)
    assert not eng.active and eng.scheduler.backlog() == 0
    validate_chrome(eng.tracer.to_chrome())
