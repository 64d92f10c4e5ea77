"""Benchmark harness — one function per claim/table (CSV to stdout).

The paper is theory-only (no experiment tables), so the benches validate
its RESULT statements empirically and measure the systems layers built
on them:

  result1_worst_case_steps   — O(1) allocate/free (Result 1.2)
  result1_vs_baselines       — worst-case steps vs lock / Treiber
  result1_space_overhead     — Theta(p^2) metadata (Result 1.4)
  result1_memory_blowup      — vs Hoard-style Theta(p*S) (section 3.1)
  result2_shared_op_cost     — O(p) shared stack ops (Result 2.1)
  jax_block_pool_o1          — device pool: alloc AND chunked alloc_n
                               cost independent of m
  jax_paged_kv_append        — paged KV append + append_chunk throughput
  serving_throughput         — continuous-batching engine tok/s:
                               width-1 token lanes (chunk=1, the
                               single-token baseline the deleted
                               legacy path degenerated to) vs full
                               chunked lanes, on a decode-heavy and a
                               prompt-heavy mix, in the same run
  serving_pool_churn         — many short requests with a hot ~90%-shared
                               prompt prefix: prefix sharing (refcounted
                               pages + COW, DESIGN.md §7) vs unshared,
                               pages-in-use reduction and token identity
  serving_overload           — bursty arrivals at 2x slot capacity with an
                               80% hot-prefix mix and an interactive SLO
                               class landing mid-burst (DESIGN.md §8):
                               p50/p99 latency, prefix hit rate,
                               preemption count, and the prefill work the
                               pinned prefix cache saves across
                               drain-to-idle gaps vs pinning disabled —
                               token-identical to an unconstrained run,
                               zero leaks after drain + pin flush
  serving_speculative        — accept-regime sweep for speculative
                               decode (DESIGN.md §10, §12): the same
                               80%-hot-prefix greedy trace run under
                               full-accept / partial-accept /
                               adversarial all-reject draft streams at
                               each draft_len, reporting accept rate,
                               speedup_gen vs one shared non-spec
                               baseline, the measured break-even accept
                               rate per draft_len, and gated rows
                               showing the accept-rate gate recovering
                               throughput on hostile streams
  serving_mesh_shards        — dp=4 engine on the shard_map allocation
                               plane (DESIGN.md §9).  Needs >= 4
                               devices in this process; with fewer it
                               records an explicit ``skipped_no_mesh``
                               marker, never the vmap fallback's numbers

CLI modes (besides the default full run):
  --emit-json NAME   run one serving bench and print its row as a
                     ``BENCH_JSON:{...}`` line
  --spec-smoke       CI gate: assert speedup_gen >= 1.0 on the gated
                     partial-accept mix and write a jax.profiler trace
                     of a speculative step to spec_trace/

Output: ``name,us_per_call,derived`` CSV rows, plus machine-readable
``BENCH_serving.json`` (written next to the CWD) so the serving perf
trajectory is tracked across PRs.
"""

import json
import os
import random
import statistics
import sys
import time

# repo root on sys.path: result2_shared_op_cost borrows a helper from
# tests/, which `python benchmarks/run.py` would otherwise not resolve
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _time_us(fn, n=5):
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        ts.append((time.perf_counter() - t0) * 1e6)
    return statistics.median(ts)


def result1_worst_case_steps():
    from repro.core import SimContext, WaitFreeAllocator, Scheduler
    worst = {}
    us = 0.0
    for p in (2, 4, 8, 16, 32):
        ctx = SimContext(p, seed=0)
        alloc = WaitFreeAllocator(ctx, shared_batches=4 * p)
        sched = Scheduler(seed=0)

        def phased(pid, alloc=alloc):
            # alloc/free bursts sized to force shared-pool transfers
            held = []
            for ph in range(4):
                if ph % 2 == 0:
                    for _ in range(alloc.ell * 3):
                        held.append((yield from alloc.allocate(pid)))
                else:
                    while held:
                        yield from alloc.free(pid, held.pop())

        for pid in range(p):
            sched.add(pid, phased(pid))
        t0 = time.perf_counter()
        sched.run("random")
        us = (time.perf_counter() - t0) * 1e6 / max(len(ctx.history), 1)
        worst[p] = max(op.steps for op in ctx.history if op.completed)
    derived = "worst_steps_by_p=" + "/".join(
        f"{p}:{w}" for p, w in worst.items())
    print(f"result1_worst_case_steps,{us:.2f},{derived}")
    return worst


def result1_vs_baselines():
    from repro.core import SimContext, Scheduler
    from repro.core.baselines import LockFreeListAllocator, TreiberAllocator
    p = 8
    rows = {}
    for name, cls in (("lock", LockFreeListAllocator),
                      ("treiber", TreiberAllocator)):
        ctx = SimContext(p, seed=0)
        alloc = cls(ctx, m=4096)
        sched = Scheduler(seed=0)

        def workload(pid, alloc=alloc):
            held = []
            rng = random.Random(pid)
            for _ in range(150):
                if not held or (len(held) < 16 and rng.random() < 0.6):
                    b = yield from alloc.allocate(pid)
                    if b >= 0:
                        held.append(b)
                else:
                    yield from alloc.free(pid, held.pop())

        for pid in range(p):
            sched.add(pid, workload(pid))
        sched.run("bursty")
        rows[name] = max(op.steps for op in ctx.history if op.completed)
    from repro.core import WaitFreeAllocator, closed_loop
    ctx = SimContext(p, seed=0)
    ours = WaitFreeAllocator(ctx, shared_batches=4 * p)
    sched = Scheduler(seed=0)
    for pid in range(p):
        sched.add(pid, closed_loop(pid, ours, 150, random.Random(pid),
                                   scribble=False))
    sched.run("bursty")
    rows["ours"] = max(op.steps for op in ctx.history if op.completed)
    print(f"result1_vs_baselines,0,"
          f"worst_steps ours={rows['ours']} lock={rows['lock']} "
          f"treiber={rows['treiber']}")
    return rows


def result1_space_overhead():
    from repro.core import SimContext, WaitFreeAllocator
    words = {}
    for p in (2, 4, 8, 16, 32, 64):
        ctx = SimContext(p, seed=0)
        alloc = WaitFreeAllocator(ctx, shared_batches=4 * p)
        words[p] = alloc.metadata_words()
    # quadratic fit sanity: words(2p)/words(p) -> 4 as p grows
    ratio = words[64] / words[32]
    derived = ("words_by_p=" + "/".join(f"{p}:{w}" for p, w in words.items())
               + f" growth_ratio_64v32={ratio:.2f}")
    print(f"result1_space_overhead,0,{derived}")
    return words


def result1_memory_blowup():
    from repro.core.baselines import HoardSpaceModel
    rows = []
    for p in (8, 64, 256):
        hoard = HoardSpaceModel(p, superblock_blocks=1024)  # 4KB/4-word blk
        ours = HoardSpaceModel.paper_blowup_blocks(p)
        rows.append(f"p{p}:ours={ours},hoard={hoard.additive_blowup_blocks()}")
    print(f"result1_memory_blowup,0,additive_blocks {' '.join(rows)}")


def result2_shared_op_cost():
    from repro.core import SimContext, Scheduler
    from tests.test_core_psim import make_stack
    costs = {}
    for p in (2, 4, 8, 16):
        ctx = SimContext(p, seed=0)
        stack, _, _ = make_stack(ctx, nodes_per_proc=8 * p + 16)
        sched = Scheduler(seed=0)
        worst = [0]

        def worker(pid, worst=worst, stack=stack, ctx=ctx):
            for i in range(5):
                rec = ctx.begin_op(pid, "push")
                yield from stack.push(pid, pid * 100 + i)
                ctx.end_op(rec)
                worst[0] = max(worst[0], rec.steps)

        for pid in range(p):
            sched.add(pid, worker(pid))
        sched.run("random")
        costs[p] = worst[0]
    derived = ("push_steps_by_p=" + "/".join(f"{p}:{c}" for p, c in costs.items())
               + " (linear in p)")
    print(f"result2_shared_op_cost,0,{derived}")


def jax_block_pool_o1():
    """alloc and chunked alloc_n cost vs pool size m (donated buffers so
    the free-stack is updated in place, as the serving step does — an
    un-donated jit would copy the m-sized stack and mask the O(R) op)."""
    import jax
    import jax.numpy as jnp
    from repro.core import block_pool

    def timed_pairs(m, step):
        pool = block_pool.create(m)
        pool = step(pool)                        # compile
        jax.block_until_ready(pool.top)
        ts = []
        for _ in range(20):
            t0 = time.perf_counter()
            pool = step(pool)
            jax.block_until_ready(pool.top)
            ts.append((time.perf_counter() - t0) * 1e6)
        return statistics.median(ts)

    counts = jnp.tile(jnp.asarray([2, 0, 3, 1], jnp.int32), 16)  # 64 slots
    mask = jnp.ones(64, bool)
    us_by_m, usn_by_m = {}, {}
    for m in (1 << 10, 1 << 14, 1 << 18):
        alloc = jax.jit(block_pool.alloc, donate_argnums=(0,))
        alloc_n = jax.jit(block_pool.alloc_n, static_argnums=(2,),
                          donate_argnums=(0,))
        freef = jax.jit(block_pool.free, donate_argnums=(0,))

        def pair(pool, alloc=alloc, freef=freef):
            pool, ids = alloc(pool, mask)
            return freef(pool, ids)

        def pair_n(pool, alloc_n=alloc_n, freef=freef):
            pool, ids = alloc_n(pool, counts, 4)
            return freef(pool, ids.reshape(-1))

        us_by_m[m] = timed_pairs(m, pair)
        usn_by_m[m] = timed_pairs(m, pair_n)
    derived = ("us_by_pool_size=" + "/".join(
        f"{m}:{u:.1f}" for m, u in us_by_m.items())
        + " alloc_n_us_by_pool_size=" + "/".join(
        f"{m}:{u:.1f}" for m, u in usn_by_m.items()))
    print(f"jax_block_pool_o1,{us_by_m[1 << 18]:.2f},{derived}")


def jax_paged_kv_append():
    import jax
    import jax.numpy as jnp
    from repro.core import kv_cache
    cache = kv_cache.create(num_pages=256, page_size=16, kv_heads=4,
                            head_dim=64, max_seqs=16, max_pages_per_seq=16)
    app = jax.jit(kv_cache.append)
    appc = jax.jit(kv_cache.append_chunk)
    k = jnp.ones((16, 4, 64))
    v = jnp.ones((16, 4, 64))
    act = jnp.ones(16, bool)
    C = 16
    kc = jnp.ones((16, C, 4, 64))
    vc = jnp.ones((16, C, 4, 64))
    lens = jnp.full((16,), C, jnp.int32)
    jax.block_until_ready(app(cache, k, v, act)[1])          # compile
    jax.block_until_ready(appc(cache, kc, vc, lens)[1])
    us = _time_us(lambda: jax.block_until_ready(app(cache, k, v, act)[1]),
                  n=20)
    usc = _time_us(lambda: jax.block_until_ready(
        appc(cache, kc, vc, lens)[1]), n=20)
    print(f"jax_paged_kv_append,{us:.2f},tokens_per_call=16 "
          f"chunk_us={usc:.2f} chunk_tokens_per_call={16 * C} "
          f"chunk_us_per_token={usc / (16 * C):.3f}")


def _run_serving_mix(cfg, params, prompts, max_new, chunk):
    from repro.serving.engine import Request, ServingEngine
    eng = ServingEngine(cfg, params, dp=2, b_local=2, max_len=96,
                        chunk_size=chunk)
    # warmup: compile every step shape (chunk prefill, T=1 decode,
    # release) off the clock
    w = Request(-1, prompt=list(range(2, 2 + chunk + 2)), max_new_tokens=2)
    eng.submit(w)
    eng.run(max_steps=100)
    eng.stats.update(steps=0, tokens_out=0, prompt_tokens=0)
    for i, p in enumerate(prompts):
        eng.submit(Request(i, prompt=list(p), max_new_tokens=max_new))
    t0 = time.perf_counter()
    eng.run(max_steps=4000)
    dt = time.perf_counter() - t0
    total = eng.stats["tokens_out"] + eng.stats["prompt_tokens"]
    return {
        "gen_tok_per_s": round(eng.stats["tokens_out"] / dt, 1),
        "total_tok_per_s": round(total / dt, 1),
        "steps": eng.stats["steps"],
        "us_per_step": round(dt * 1e6 / max(eng.stats["steps"], 1)),
        "wall_s": round(dt, 3),
        "alloc_O1_max": eng.stats["alloc_steps_max"],
        "leak_free": eng.page_occupancy() == 0.0,
        "telemetry": eng.telemetry.snapshot(),
    }


def serving_throughput():
    """Width-1 vs chunked token lanes on decode-heavy and prompt-heavy
    mixes (same params, same run) + BENCH_serving.json for trend
    tracking.  chunk=1 runs the SAME unified step one token per lane
    per step — the baseline the deleted legacy path degenerated to —
    so the A/B now isolates exactly the lane-width win."""
    import numpy as np
    import jax
    from repro import models
    from repro.configs import get_config, smoke_config
    cfg = smoke_config(get_config("olmo-1b"))
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    chunk = 16
    mixes = {
        # prompt len >= 4x generation len: chunked prefill dominates
        "prompt_heavy": ([list(rng.randint(1, 255, 48)) for _ in range(12)], 8),
        "decode_heavy": ([list(rng.randint(1, 255, 6)) for _ in range(12)], 24),
    }
    report = {"config": cfg.name, "chunk_size": chunk, "mixes": {}}
    for mix, (prompts, max_new) in mixes.items():
        width1 = _run_serving_mix(cfg, params, prompts, max_new, chunk=1)
        chunked = _run_serving_mix(cfg, params, prompts, max_new,
                                   chunk=chunk)
        speedup = (chunked["total_tok_per_s"] /
                   max(width1["total_tok_per_s"], 1e-9))
        report["mixes"][mix] = {"width1": width1, "chunked": chunked,
                                "speedup_total": round(speedup, 2)}
        print(f"serving_throughput,{chunked['us_per_step']},mix={mix} "
              f"chunked_tok_per_s={chunked['total_tok_per_s']} "
              f"width1_tok_per_s={width1['total_tok_per_s']} "
              f"speedup={speedup:.2f}x steps={chunked['steps']} "
              f"alloc_O1_max={chunked['alloc_O1_max']}")
    report["mixes"]["pool_churn"] = serving_pool_churn(cfg, params)
    report["mixes"]["overload"] = serving_overload(cfg, params)
    report["mixes"]["mesh_shards"] = serving_mesh_shards(cfg, params)
    report["mixes"]["speculative"] = serving_speculative(cfg, params)
    report["mixes"]["chaos"] = serving_chaos(cfg, params)
    report["mixes"]["size_classes"] = serving_size_classes(cfg, params)
    report["mixes"]["moe"] = serving_moe(cfg, params)
    with open("BENCH_serving.json", "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    return report


def _garble(t):
    """A token guaranteed != t for any t in [0, 255] (vocab stays 1..255,
    clear of pad/EOS ids): t == ((t+1) % 255) + 1 would need 2 ≡ 0
    (mod 255)."""
    return ((int(t) + 1) % 255) + 1


def _break_even_accept(eng, k):
    """Smallest accept rate a where 1 + a + ... + a^k clears the
    measured spec-step cost ratio cost(k+1, spec)/cost(1, decode) — the
    same break-even the engine's ``_gate_k`` applies (DESIGN.md §12).
    Falls back to the linear cost model when a width is unmeasured."""
    c1 = eng._step_cost.get((1, False))
    ck = eng._step_cost.get((k + 1, True))
    measured = bool(c1 and ck)
    ratio = (ck / c1) if measured else 1.0 + eng.spec_cost_slope * k
    if k + 1 < ratio:                 # even accept=1.0 can't pay
        return {"cost_ratio": round(ratio, 3), "measured": measured,
                "accept": None}
    lo, hi = 0.0, 1.0
    for _ in range(40):
        mid = 0.5 * (lo + hi)
        if sum(mid ** i for i in range(k + 1)) >= ratio:
            hi = mid
        else:
            lo = mid
    return {"cost_ratio": round(ratio, 3), "measured": measured,
            "accept": round(hi, 3)}


def serving_speculative(cfg, params, smoke=False):
    """Accept-regime sweep for speculative decode (DESIGN.md §10, §12).

    One shared non-speculative baseline engine runs an 80%-hot-prefix
    greedy trace (and is re-measured next to every regime row, so each
    speedup compares thermally-local machine states), then the same
    trace runs under three draft-stream regimes at each draft_len — ``full`` (recorded history is right: accept 1.0),
    ``partial`` (history right for the first ~3/4 of each request's
    continuation, then wrong — the boundary draft is partially
    accepted and rolls back its rejected tail, and the per-prefix
    accept EWMA stays clearly above the gate's break-even),
    ``adversarial`` (history wrong from the first draft token:
    all-reject, every draft page rolled back) — with the accept-rate
    gate OFF so the regime's raw cost/benefit is what's measured.  Each
    draft_len also reports its measured break-even accept rate (the
    gate's decision boundary, from the EWMA step-cost model).  Two
    gated rows close the loop: ``partial_gated`` must pay
    (speedup_gen >= 1.0 is the CI spec-perf-smoke gate) and
    ``adversarial_gated`` shows the gate switching hostile prefixes off
    and recovering ~baseline throughput.  Token identity vs the
    baseline holds in EVERY regime — verification guarantees output,
    regimes only move cost."""
    import numpy as np
    from repro.serving.engine import Request, ServingEngine

    rng = np.random.RandomState(0)
    hot = list(rng.randint(1, 255, 16))                  # 2 pages of 8
    uniq = [hot + list(rng.randint(1, 255, 4 + i)) for i in range(4)]
    trace = []
    for i in range(24):
        if rng.random_sample() < 0.8:
            trace.append(list(uniq[rng.randint(len(uniq))]))  # hot repeat
        else:
            trace.append(list(rng.randint(1, 255, 8 + i % 9)))
    # decode-heavy generations: at max_new=8 the trace wall time is
    # ~70% chunked prefill (where drafts can't help) and the real
    # decode-side win drowns in prefill-step timing variance; 24 new
    # tokens per request puts the measurement where speculation acts
    mn = 24

    def drive(eng, reqs, max_steps=2000):
        t0 = time.perf_counter()
        for r in reqs:
            eng.submit(r)
        eng.run(max_steps=max_steps)
        dt = time.perf_counter() - t0
        assert all(r.done for r in reqs)
        return dt

    def reset(eng):
        for k in ("steps", "tokens_out", "prompt_tokens", "spec_lanes",
                  "spec_drafted", "spec_accepted", "spec_pages_rolled_back",
                  "spec_gate_skips", "spec_mixed_steps"):
            eng.stats[k] = 0
        eng.stats["accept_hist"] = {}
        eng.stats["chunk_hist"] = {}

    def run_trace(eng, rid0, passes=3):
        """Best-of-N measured passes.  A single trace is only ~50 steps
        (~0.2s on the smoke config), so one stray jit compile or OS
        hiccup inside the window swings the ratio by 30%+; pass 1
        absorbs any residual compile, later passes are steady state,
        and the fastest pass is reported.  The regimes are stationary
        (recording is stubbed), so every pass must be token-identical —
        asserted — which also pins tokens_out across passes, keeping
        the last pass's stats consistent with the best pass's dt."""
        outs0, best_dt = None, None
        for p in range(passes):
            reset(eng)
            reqs = [Request(rid0 + 100 * p + i, prompt=list(pr),
                            max_new_tokens=mn)
                    for i, pr in enumerate(trace)]
            dt = drive(eng, reqs)
            outs = [r.out_tokens for r in reqs]
            assert outs0 is None or outs == outs0, \
                f"trace pass {p} diverged from pass 0"
            outs0 = outs
            best_dt = dt if best_dt is None else min(best_dt, dt)
        return outs0, best_dt

    def stagger_warm(eng):
        """Replay the hot prompts with OVERLAPPING lifetimes: a repeat
        admitted while its twin is live takes the prefix-share path,
        whose jitted admission step otherwise compiles (~0.5s) inside
        the first measured run — every engine warms through this so
        trace runs compare steady states, not compile schedules."""
        reqs = [Request(-101 - i, prompt=list(p), max_new_tokens=mn)
                for i, p in enumerate(uniq)]
        for r in reqs[:2]:
            eng.submit(r)
        for _ in range(3):
            eng.step()
        for r in reqs[2:]:
            eng.submit(r)
        eng.run(max_steps=500)
        assert all(r.done for r in reqs)

    def prep(draft_len, gate, regime):
        """Engine warmed off the clock (pass 1 records the true
        continuations and captures them for poisoning, pass 2 replays
        so the spec variant compiles and the step-cost EWMA gets real
        samples), then the hot streams are rewritten for the regime and
        recording is stubbed so trace completions can't heal them."""
        eng = ServingEngine(cfg, params, dp=1, b_local=4, max_len=96,
                            chunk_size=16, speculate=True,
                            draft_len=draft_len, spec_gate=gate)
        # exact-replay-only drafting for the regime probes: all hot
        # prompts share ONE key, and once a request crosses the
        # poisoned boundary the n-gram fallback would keep
        # extrapolating garbage from the same streams — its 0-accept
        # lanes collapse the per-key EWMA that also gates the still-
        # reliable exact replay of fresh requests, so the gate's
        # on/off state becomes an order-dependent coin flip instead of
        # a property of the regime.  The sweep isolates replay
        # economics; n-gram drafting has its own identity/rollback
        # tests.
        eng.spec_store.ngram = 0
        reqs = [Request(-1 - i, prompt=list(p), max_new_tokens=mn)
                for i, p in enumerate(uniq)]
        drive(eng, reqs, max_steps=500)
        real = {tuple(p): list(r.out_tokens) for p, r in zip(uniq, reqs)}
        # replay staggered: the first pair decodes (drafts live from the
        # streams pass 1 recorded) while the second pair's prompts are
        # still pending, so the MIXED prompt/decode spec width AND the
        # prefix-share admission path compile here, not on the trace
        stagger_warm(eng)
        if regime != "full":
            # prefill emits each prompt's first token before any draft
            # fires, so a poisoned stream needs >= 1 true token for the
            # exact-suffix replay to engage at all; after that the
            # adversarial stream is pure garbage (0 accepts/draft)
            # while the partial stream stays right for the first ~3/4
            # of the continuation and garbage after — replay goes
            # structurally dead at the first garbled position (the
            # engine's correction token diverges the request suffix
            # from the stream), so the boundary draft is the partially
            # accepted one that pays a rejected-tail rollback, and the
            # accept EWMA lands clearly above the gate's measured
            # break-even (~0.3-0.4 on the smoke config; a stream
            # that is only half-a-draft right sits ON that boundary
            # and the gate legitimately oscillates — noise, not
            # signal).  All hot prompts share ONE whole-page key
            # (their page-aligned prefix is the same 2 hot pages), so
            # clear that key's streams once, THEN record every
            # prompt's poisoned stream under it — the per-suffix
            # replay disambiguates.
            for key in {eng.spec_store.key_of(p) for p in uniq}:
                eng.spec_store.streams.pop(key, None)
            for p in uniq:
                key = eng.spec_store.key_of(p)
                tail = tuple(p[len(key):])
                r = real[tuple(p)]
                keep = (1 if regime == "adversarial"
                        else 1 + max(1, 3 * len(r) // 4))
                cont = tail + tuple(r[:keep]) + tuple(
                    _garble(r[j]) if j < len(r) else _garble(j + 31)
                    for j in range(keep, keep + 8))
                eng.spec_store.record(key, cont)
            eng.spec_store.record = lambda *a, **kw: None
        reset(eng)
        return eng

    def row_of(eng, dt, outs, base_now):
        s = eng.stats
        tps = round(s["tokens_out"] / dt, 1)
        return {
            "steps": s["steps"],
            "spec_lanes": s["spec_lanes"],
            "drafted": s["spec_drafted"],
            "accepted": s["spec_accepted"],
            "accept_rate": round(s["spec_accepted"]
                                 / max(s["spec_drafted"], 1), 2),
            "gate_skips": s["spec_gate_skips"],
            "mixed_steps": s["spec_mixed_steps"],
            "pages_rolled_back": s["spec_pages_rolled_back"],
            "gen_tok_per_s": tps,
            "baseline_tok_per_s": base_now,
            "speedup_gen": round(tps / max(base_now, 1e-9), 2),
            "token_identical": outs == base_outs,
            "leak_free": eng.page_occupancy() == 0.0,
            "telemetry": eng.telemetry.snapshot(),
        }

    # ---- shared baseline: one non-speculative engine, kept alive so
    # every regime row can re-measure it in the SAME machine state.
    # The sweep interleaves minutes of multi-core jit compilation with
    # its measured windows; a baseline captured once up front is 20-40%
    # stale (thermal/frequency drift) by the later rows, which showed
    # up as two behaviorally identical regime runs differing 0.75x vs
    # 1.04x purely by when they ran.
    base_eng = ServingEngine(cfg, params, dp=1, b_local=4, max_len=96,
                             chunk_size=16)
    drive(base_eng, [Request(-1 - i, prompt=list(p), max_new_tokens=mn)
                     for i, p in enumerate(uniq)], max_steps=500)  # compile
    stagger_warm(base_eng)
    base_outs, base_dt = run_trace(base_eng, 0)
    base_tps = round(base_eng.stats["tokens_out"] / base_dt, 1)
    base = {"steps": base_eng.stats["steps"], "gen_tok_per_s": base_tps,
            "leak_free": base_eng.page_occupancy() == 0.0}
    _base_rid = [10000]

    def base_tps_now():
        """Thermally-local baseline: best-of-2 fresh passes on the
        warmed baseline engine, taken right after the regime row it
        normalizes."""
        _base_rid[0] += 1000
        outs, dt = run_trace(base_eng, _base_rid[0], passes=2)
        assert outs == base_outs
        return round(base_eng.stats["tokens_out"] / dt, 1)

    def gated_run(regime, rid0):
        eng = prep(4, True, regime)
        outs, dt = run_trace(eng, rid0)
        r = row_of(eng, dt, outs, base_tps_now())
        r["break_even"] = _break_even_accept(eng, 4)
        print(f"serving_speculative,0,regime={regime}_gated draft_len=4 "
              f"accept_rate={r['accept_rate']} "
              f"speedup_gen={r['speedup_gen']} "
              f"gate_skips={r['gate_skips']} "
              f"token_identical={r['token_identical']} "
              f"leak_free={r['leak_free']}")
        return r

    if smoke:
        partial_gated = gated_run("partial", 3000)
        return {"baseline": base, "partial_gated": partial_gated}

    # ---- raw regimes, gate off: what each accept regime really costs
    sweep = {}
    for dl in (2, 4):
        regimes = {}
        for regime in ("full", "partial", "adversarial"):
            eng = prep(dl, False, regime)
            outs, dt = run_trace(eng, 1000 * dl)
            regimes[regime] = row_of(eng, dt, outs, base_tps_now())
            print(f"serving_speculative,0,regime={regime} draft_len={dl} "
                  f"accept_rate={regimes[regime]['accept_rate']} "
                  f"speedup_gen={regimes[regime]['speedup_gen']} "
                  f"rolled_back={regimes[regime]['pages_rolled_back']} "
                  f"token_identical={regimes[regime]['token_identical']} "
                  f"leak_free={regimes[regime]['leak_free']}")
        regimes["break_even"] = _break_even_accept(eng, dl)
        print(f"serving_speculative,0,break_even draft_len={dl} "
              f"cost_ratio={regimes['break_even']['cost_ratio']} "
              f"accept={regimes['break_even']['accept']} "
              f"measured={regimes['break_even']['measured']}")
        sweep[f"draft_len_{dl}"] = regimes

    # ---- gate on: partial must pay, adversarial must be defanged
    partial_gated = gated_run("partial", 3000)
    adversarial_gated = gated_run("adversarial", 4000)

    all_identical = (partial_gated["token_identical"]
                     and adversarial_gated["token_identical"]
                     and all(r["token_identical"]
                             for k, regs in sweep.items()
                             for n, r in regs.items() if n != "break_even"))
    row = {"baseline": base, "sweep": sweep,
           "partial_gated": partial_gated,
           "adversarial_gated": adversarial_gated,
           "token_identical": all_identical}
    print(f"serving_speculative,0,summary baseline={base_tps}tok/s "
          f"partial_gated_speedup={partial_gated['speedup_gen']} "
          f"adversarial_gated_speedup={adversarial_gated['speedup_gen']} "
          f"token_identical_all_regimes={all_identical}")
    return row


_JSON_TAG = "BENCH_JSON:"


def serving_mesh_shards(cfg, params):
    """Multi-host allocation plane smoke (DESIGN.md §9) — with the
    mesh actually present.  A dp=4 engine only shard_maps over a real
    ("dp",) device mesh when the process has >= 4 devices; below that
    it would fall back to vmap semantics, so the row is an explicit
    ``skipped_no_mesh`` marker instead of single-device numbers.  Give
    the process >= 4 devices to measure it (on the CPU,
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``, as CI's
    mesh-8 job does).  No child process is started: a parent holding an
    accelerator would leave the child without one.

    Reports the per-shard occupancy stats from the packed status row
    (the scheduler's placement balance across hosts) and the usual
    leak/identity axes vs a single-device run of the same trace."""
    import jax
    n_dev = jax.local_device_count()
    if n_dev < 4:
        print(f"serving_mesh_shards,0,skipped_no_mesh=True "
              f"devices={n_dev} (dp=4 would fall back to single-device "
              "vmap semantics; row suppressed)")
        return {"skipped_no_mesh": True, "mesh_devices": n_dev}
    import numpy as np
    from repro.serving.engine import Request, ServingEngine
    from repro.serving.sched import SchedConfig

    rng = np.random.RandomState(0)
    hot = list(rng.randint(1, 255, 24))                  # 3 pages of 8
    spec = []
    for i in range(20):
        if rng.random_sample() < 0.6:
            prompt = hot + list(rng.randint(1, 255, 2 + i % 5))
        else:
            prompt = list(rng.randint(1, 255, 8 + i % 9))
        spec.append(prompt)

    def run(dp, b_local):
        eng = ServingEngine(cfg, params, dp=dp, b_local=b_local,
                            max_len=64, chunk_size=16,
                            sched=SchedConfig(pin_pages=8))
        reqs = [Request(i, prompt=list(p), max_new_tokens=4)
                for i, p in enumerate(spec)]
        t0 = time.perf_counter()
        for r in reqs:
            eng.submit(r)
        eng.run(max_steps=1000)
        dt = time.perf_counter() - t0
        assert all(r.done for r in reqs)
        eng.flush_pins()
        return [r.out_tokens for r in reqs], eng, dt

    out4, eng4, dt = run(dp=4, b_local=2)
    out1, eng1, _ = run(dp=1, b_local=2)
    occ = eng4.shard_occupancy()
    row = {
        "mesh_devices": occ["mesh_devices"],
        "shard_map": eng4.mesh is not None,
        "gen_tok_per_s": round(eng4.stats["tokens_out"] / dt, 1),
        "steps": eng4.stats["steps"],
        "pages_mean_shard": occ["pages_mean_shard"],
        "pages_peak_shard": occ["pages_peak_shard"],
        "prefix_hit_rate": round(eng4.stats["prefix_shared_reqs"]
                                 / max(eng4.stats["admitted"], 1), 2),
        "token_identical_vs_single_device": out4 == out1,
        "leak_free": eng4.page_occupancy() == 0.0,
        "telemetry": eng4.telemetry.snapshot(),
    }
    print(f"serving_mesh_shards,0,devices={row['mesh_devices']} "
          f"shard_map={row['shard_map']} "
          f"pages_mean_shard={row['pages_mean_shard']} "
          f"pages_peak_shard={row['pages_peak_shard']} "
          f"token_identical={row['token_identical_vs_single_device']} "
          f"leak_free={row['leak_free']}")
    return row


def serving_pool_churn(cfg, params):
    """Pool-churn scenario: a stream of short requests sharing a hot
    ~90% prompt prefix (the production shape: one system prompt, many
    users).  Measures prefix sharing's pages-in-use win at equal
    outputs — the refactor's acceptance bar is >= 2x fewer mean
    pages-in-use with token-identical generations."""
    import numpy as np
    from repro.serving.engine import Request, ServingEngine
    rng = np.random.RandomState(0)
    hot = list(rng.randint(1, 255, 68))                    # 8.5 pages of 8
    prompts = [hot + list(rng.randint(1, 255, 6)) for _ in range(16)]

    def run(share):
        eng = ServingEngine(cfg, params, dp=1, b_local=6, max_len=96,
                            chunk_size=16, prefix_sharing=share)
        # warm the hot prefix: the first request prefills it, then the
        # arrival stream overlaps lifetimes (continuous batching)
        reqs = [Request(0, prompt=list(prompts[0]), max_new_tokens=8)]
        eng.submit(reqs[0])
        for _ in range(5):
            eng.step()
        t0 = time.perf_counter()
        for i, p in enumerate(prompts[1:], 1):
            r = Request(i, prompt=list(p), max_new_tokens=8)
            reqs.append(r)
            eng.submit(r)
        eng.run(max_steps=2000)
        dt = time.perf_counter() - t0
        assert all(r.done for r in reqs)
        # shared prompt tokens were SERVED without being fed — count
        # them in delivered throughput or the shared run looks slower
        # for doing strictly less work per request
        total = (eng.stats["tokens_out"] + eng.stats["prompt_tokens"]
                 + eng.stats["prefix_shared_tokens"])
        return [r.out_tokens for r in reqs], {
            "delivered_tok_per_s": round(total / dt, 1),
            "steps": eng.stats["steps"],
            "pages_mean": round(eng.pages_mean(), 1),
            "pages_peak": eng.stats["pages_peak"],
            "prefix_shared_tokens": eng.stats["prefix_shared_tokens"],
            "prefix_shared_reqs": eng.stats["prefix_shared_reqs"],
            "leak_free": eng.page_occupancy() == 0.0,
            "telemetry": eng.telemetry.snapshot(),
        }

    out_u, unshared = run(False)
    out_s, shared = run(True)
    ratio = unshared["pages_mean"] / max(shared["pages_mean"], 1e-9)
    row = {"unshared": unshared, "shared": shared,
           "pages_mean_reduction": round(ratio, 2),
           "token_identical": out_u == out_s}
    print(f"serving_pool_churn,0,pages_mean unshared={unshared['pages_mean']} "
          f"shared={shared['pages_mean']} reduction={ratio:.2f}x "
          f"token_identical={out_u == out_s} "
          f"shared_tokens={shared['prefix_shared_tokens']} "
          f"delivered_tok_per_s shared={shared['delivered_tok_per_s']} "
          f"unshared={unshared['delivered_tok_per_s']}")
    return row


def serving_overload(cfg, params):
    """Bursty-overload scenario (DESIGN.md §8): each burst submits 2x
    the slot capacity, 80% of prompts share a hot 5-page prefix, and an
    interactive-class pair lands mid-burst (forcing preemption of
    standard work).  The engine fully drains between bursts, so without
    pinning the hot prefix dies with each burst's last request and the
    next burst re-prefills it from scratch — the pinned run re-shares
    it across the idle gap.  Acceptance axes: token identity with an
    unconstrained run, zero leaks after drain + pin flush, and a
    measured prefill-work reduction from pinning."""
    import numpy as np
    from repro.serving.engine import Request, ServingEngine
    from repro.serving.sched import SchedConfig

    rng = np.random.RandomState(0)
    hot = list(rng.randint(1, 255, 40))                  # 5 pages of 8
    spec = []
    for i in range(24):
        if rng.random_sample() < 0.8:
            prompt = hot + list(rng.randint(1, 255, 4 + i % 5))
        else:
            prompt = list(rng.randint(1, 255, 12 + i % 7))
        spec.append((prompt, "interactive" if i % 4 == 3 else "standard"))

    def run(b_local, pin_pages, bursts):
        eng = ServingEngine(cfg, params, dp=1, b_local=b_local, max_len=96,
                            chunk_size=16,
                            sched=SchedConfig(pin_pages=pin_pages))
        reqs = [Request(i, prompt=list(p), max_new_tokens=6, slo=slo)
                for i, (p, slo) in enumerate(spec)]
        per = -(-len(reqs) // bursts)
        t0 = time.perf_counter()
        for j in range(0, len(reqs), per):
            burst = reqs[j:j + per]
            # standard work first; interactive arrives mid-burst, after
            # the slots have filled — the preemption trigger
            for r in burst:
                if r.slo != "interactive":
                    eng.submit(r)
            for _ in range(2):
                eng.step()
            for r in burst:
                if r.slo == "interactive":
                    eng.submit(r)
            eng.run(max_steps=1000)              # drain to idle
        dt = time.perf_counter() - t0
        assert all(r.done for r in reqs)
        pinned_steady = eng.pinned_pages()
        eng.flush_pins()
        lat = eng.latency_quantiles()
        s = eng.stats
        return [r.out_tokens for r in reqs], {
            "gen_tok_per_s": round(s["tokens_out"] / dt, 1),
            "steps": s["steps"],
            "p50_ms": round(lat["p50_s"] * 1e3, 1),
            "p99_ms": round(lat["p99_s"] * 1e3, 1),
            "first_token_p50_ms": round(lat["first_token_p50_s"] * 1e3, 1),
            "prompt_tokens": s["prompt_tokens"],
            "prefix_shared_tokens": s["prefix_shared_tokens"],
            "prefix_hit_rate": round(
                s["prefix_shared_reqs"] / max(s["admitted"], 1), 2),
            "pin_hits": s["pin_hit_reqs"],
            "preemptions": s["preemptions"],
            "deferred": eng.scheduler.stats["deferred"],
            "pinned_pages_steady": pinned_steady,
            "leak_free": eng.page_occupancy() == 0.0,
            "telemetry": eng.telemetry.snapshot(),
        }

    out_ref, _ = run(b_local=8, pin_pages=0, bursts=1)   # unconstrained
    out_pin, pinned = run(b_local=4, pin_pages=12, bursts=3)
    out_raw, nopin = run(b_local=4, pin_pages=0, bursts=3)
    saved = nopin["prompt_tokens"] - pinned["prompt_tokens"]
    row = {"pinned": pinned, "unpinned": nopin,
           "token_identical": out_pin == out_ref and out_raw == out_ref,
           "prefill_tokens_saved_by_pinning": saved,
           "prefill_pages_saved_by_pinning": saved // cfg.page_size}
    print(f"serving_overload,0,2x-burst 80%-hot: p50={pinned['p50_ms']}ms "
          f"p99={pinned['p99_ms']}ms hit_rate={pinned['prefix_hit_rate']} "
          f"preemptions={pinned['preemptions']} "
          f"prefill_pages_saved={row['prefill_pages_saved_by_pinning']} "
          f"token_identical={row['token_identical']} "
          f"leak_free={pinned['leak_free'] and nopin['leak_free']}")
    return row


def serving_chaos(cfg, params):
    """Fault-tolerance axes (DESIGN.md §11): crash the host inside the
    torn drain/refill rebalance window, rebuild the engine, reconcile
    allocator state from the device arrays + admission journal, and
    measure (a) recovery wall time, (b) token identity of the recovered
    run vs an unfaulted reference (greedy AND sampled lanes), and
    (c) warm vs cold restart — a warm restart carries pinned prefixes
    and speculation streams through the checkpoint sidecar, so the hot
    prefix needs no re-prefill."""
    import tempfile

    import numpy as np
    from repro.checkpoint.ckpt import Checkpointer
    from repro.serving import chaos
    from repro.serving.engine import Request, ServingEngine
    from repro.serving.sched import SchedConfig

    rng = np.random.RandomState(0)
    hot = list(rng.randint(1, 255, 16))                  # 2 pages of 8
    spec = [hot + list(rng.randint(1, 255, 4 + i % 5)) for i in range(8)]

    def reqs():
        return [Request(i, prompt=list(p), max_new_tokens=6,
                        temperature=0.7 if i % 2 else 0.0, seed=40 + i)
                for i, p in enumerate(spec)]

    # ---- reference: no faults
    ref_reqs = reqs()
    eng = ServingEngine(cfg, params, dp=1, b_local=4, max_len=96,
                        chunk_size=16)
    for r in ref_reqs:
        eng.submit(r)
    eng.run(max_steps=1000)
    ref_out = {r.rid: list(r.out_tokens) for r in ref_reqs}

    # ---- crash mid-rebalance, recover, finish
    journal = chaos.ServingJournal()
    injector = chaos.parse_faults("crash@4:post_sync:torn")

    def build():
        return ServingEngine(cfg, params, dp=1, b_local=4, max_len=96,
                             chunk_size=16, journal=journal,
                             injector=injector)

    eng = build()
    for r in reqs():
        eng.submit(r)
    try:
        eng.run(max_steps=1000)
        raise AssertionError("injected crash never fired")
    except chaos.HostCrash:
        pass
    t0 = time.perf_counter()
    eng, report = chaos.recover_engine(build, eng, journal)
    recovery_s = time.perf_counter() - t0
    eng.run(max_steps=1000)
    out = journal.outputs()
    crash_identical = (journal.finished() == set(ref_out)
                       and all(out[rid] == ref_out[rid] for rid in ref_out))
    crash_row = {
        "recovery_ms": round(recovery_s * 1e3, 1),
        "reconciled_pages": report["reclaimed"],
        "requeued": report["requeued"],
        "never_dry": report["never_dry"],
        "token_identical": crash_identical,
        "leak_free": eng.leak_free(),
        "telemetry": eng.telemetry.snapshot(),
    }

    # ---- warm vs cold restart: do pins/speculation survive?
    def fresh():
        return ServingEngine(cfg, params, dp=1, b_local=4, max_len=96,
                             chunk_size=16, speculate=True, draft_len=4,
                             sched=SchedConfig(pin_pages=8))

    def drive(eng, batch):
        t0 = time.perf_counter()
        for r in batch:
            eng.submit(r)
        eng.run(max_steps=1000)
        dt = time.perf_counter() - t0
        lat = eng.latency_quantiles()
        return dt, lat["first_token_p50_s"]

    def restart_stats(eng, dt):
        s = eng.stats
        return {
            "wall_s": round(dt, 3),
            "prompt_tokens": s["prompt_tokens"],
            "pin_hit_reqs": s["pin_hit_reqs"],
            "pin_hit_tokens": s["pin_hit_tokens"],
            "spec_lanes": s["spec_lanes"],
        }

    with tempfile.TemporaryDirectory() as d:
        warmup = fresh()
        drive(warmup, reqs())                      # pins hot, records spec
        ckptr = Checkpointer(d, keep=1)
        warmup.save_warm(ckptr, step=1)

        warm = fresh()
        warm.restore_warm(ckptr)
        dt_w, ftl_w = drive(warm, reqs())
        warm_row = restart_stats(warm, dt_w)
        warm_row["first_token_p50_ms"] = round(ftl_w * 1e3, 1)
        warm_ok = warm.stats["pin_hit_reqs"] > 0

        cold = fresh()
        dt_c, ftl_c = drive(cold, reqs())
        cold_row = restart_stats(cold, dt_c)
        cold_row["first_token_p50_ms"] = round(ftl_c * 1e3, 1)

    row = {
        "crash_recovery": crash_row,
        "warm_restart": warm_row,
        "cold_restart": cold_row,
        "prefill_tokens_saved_by_warm_restart":
            cold_row["prompt_tokens"] - warm_row["prompt_tokens"],
        "warm_restart_carried_pins": warm_ok,
    }
    print(f"serving_chaos,{crash_row['recovery_ms'] * 1e3:.0f},"
          f"torn-crash recovery={crash_row['recovery_ms']}ms "
          f"reconciled={crash_row['reconciled_pages']}pg "
          f"token_identical={crash_row['token_identical']} "
          f"leak_free={crash_row['leak_free']} "
          f"warm_vs_cold_prefill_saved="
          f"{row['prefill_tokens_saved_by_warm_restart']}tok "
          f"warm_pin_hits={warm_row['pin_hit_reqs']}")
    return row


def serving_size_classes(cfg, params):
    """Size-classed allocation plane (DESIGN.md §14): a bounded-state
    model (ring + recurrent layers) served with the two-class pool.

    Reports per-class blocks-in-use (peak and mean over steps) and the
    over-allocation the fine CLS_STATE granularity saves versus
    charging the same bounded state in whole KV pages — both in
    token-capacity units.  The paged-KV class is untouched (class-0
    counters match the single-class engine bit for bit, asserted in
    tests/test_classed_pool.py); the win is that admission accounts
    ring windows / recurrent blocks at quarter-page granularity."""
    import jax
    import numpy as np
    from repro import models
    from repro.configs import get_config, smoke_config
    from repro.core.classed_pool import CLS_KV, CLS_STATE
    from repro.models.transformer import (base_kind, state_blocks_per_slot,
                                          state_page_tokens)
    from repro.serving.engine import Request, ServingEngine

    scfg = smoke_config(get_config("recurrentgemma-2b"))
    sparams = models.init_params(scfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    max_len = 96
    # speculation on: the repeat share of the mix drafts from recorded
    # continuations, so draft-tail rollback traffic rides the run (on a
    # ring/recurrent arch it frees zero KV pages — state never moves,
    # which is the point of the accounting-plane routing)
    eng = ServingEngine(scfg, sparams, dp=2, b_local=2, max_len=max_len,
                        size_classes=2, speculate=True, draft_len=4)
    base = list(rng.randint(1, 255, 12))
    prompts = [list(base) if i % 3 == 0
               else list(rng.randint(1, 255, rng.randint(6, 16)))
               for i in range(10)]
    for i, p in enumerate(prompts):
        eng.submit(Request(i, prompt=p, max_new_tokens=8))
    in_use = {CLS_KV: [], CLS_STATE: []}
    t0 = time.perf_counter()
    for _ in range(2000):
        if eng.idle():
            break
        eng.step()
        for c in in_use:
            in_use[c].append(eng.blocks_in_use(c))
    dt = time.perf_counter() - t0
    assert eng.idle() and eng.leak_free()

    # over-allocation: the same bounded state charged at KV-page
    # granularity (each component rounds up to a whole coarse page)
    psz_kv, psz_s = scfg.page_size, state_page_tokens(scfg)
    W = min(scfg.window or max_len, max_len)
    coarse_pages = 0
    for k in scfg.pattern:
        bk = base_kind(k)
        if bk == "local":
            coarse_pages += scfg.n_groups * -(-W // psz_kv)
        elif bk != "global":
            coarse_pages += scfg.n_groups      # one page per rec block
    for k in scfg.remainder:
        bk = base_kind(k)
        if bk == "local":
            coarse_pages += -(-W // psz_kv)
        elif bk != "global":
            coarse_pages += 1
    sbs = state_blocks_per_slot(scfg, max_len)
    fine_tok = sbs * psz_s
    coarse_tok = coarse_pages * psz_kv
    admissions = eng.stats["admitted"]
    granted = eng.stats["state_blocks_granted"]
    assert granted == admissions * sbs, (granted, admissions, sbs)
    row = {
        "config": scfg.name,
        "size_classes": eng.n_classes,
        "state_blocks_per_slot": sbs,
        "state_page_tokens": psz_s,
        "kv_page_tokens": psz_kv,
        "blocks_in_use_peak": {c: int(max(v) if v else 0)
                               for c, v in in_use.items()},
        "blocks_in_use_mean": {c: round(float(np.mean(v)) if v else 0.0, 2)
                               for c, v in in_use.items()},
        "state_blocks_granted": granted,
        "per_slot_state_tokens_fine": fine_tok,
        "per_slot_state_tokens_coarse": coarse_tok,
        "over_alloc_saved_tokens_per_slot": coarse_tok - fine_tok,
        "over_alloc_saved_tokens_total": admissions * (coarse_tok - fine_tok),
        "saved_frac": round(1 - fine_tok / max(coarse_tok, 1), 4),
        "spec_drafted": eng.stats["spec_drafted"],
        "spec_pages_rolled_back": eng.stats["spec_pages_rolled_back"],
        "wall_s": round(dt, 3),
        "leak_free": True,
    }
    assert row["over_alloc_saved_tokens_per_slot"] > 0, (
        "fine class saved nothing — class boundary is mis-sized")
    print(f"serving_size_classes,0,arch={scfg.name} "
          f"state_blocks/slot={sbs} "
          f"saved_tok/slot={row['over_alloc_saved_tokens_per_slot']} "
          f"saved_frac={row['saved_frac']} "
          f"peak_state_blocks={row['blocks_in_use_peak'][CLS_STATE]}")
    return row


def serving_moe(cfg, params):
    """Expert-paged MoE serving (DESIGN.md §15): the expert FFN stack
    routed through the classed pool's CLS_EXPERT read-only pages vs the
    resident-weight engine, on three footprint mixes:

    * ``skewed``   — 80% of requests share one hot 2-expert footprint,
      the rest fan out to cold pairs (the production shape load-aware
      admission is built for);
    * ``uniform``  — footprints rotate round-robin over disjoint pairs
      (worst case for the LRU: every admission is a miss);
    * ``hot_repeat`` — every request reuses the same footprint (best
      case: one load, then pure hits).

    Reports expert hit rate, peak pages resident, and the weight-HBM
    savings vs full residency — and asserts the §15 soundness story:
    token-identical streams on every mix, zero in-step misses, zero
    dropped tokens, leak-free after drain + flush.  The paged engine
    runs under a budget HALF of full residency — a configuration the
    resident engine cannot express at all — and the skewed mix must
    clear the >= 30% peak weight-HBM reduction bar."""
    import dataclasses
    import jax
    import numpy as np
    from repro import models
    from repro.configs import get_config, smoke_config
    from repro.models.transformer import EXPERT_PPE, expert_layer_slots
    from repro.serving.engine import Request, ServingEngine

    scfg = smoke_config(get_config("mixtral-8x7b"))
    # serving capacity factor: dispatch capacity >= routed load, so the
    # zero-drop meter (satellite of §15) is a hard invariant here
    scfg = dataclasses.replace(
        scfg, moe=dataclasses.replace(scfg.moe, capacity_factor=64.0))
    sparams = models.init_params(scfg, jax.random.PRNGKey(0))
    E = scfg.moe.num_experts
    slots = expert_layer_slots(scfg)
    full_pages = slots * E * EXPERT_PPE          # resident-engine stack
    budget = full_pages // 2                      # inexpressible resident
    pairs = [tuple(sorted((i % E, (i + 1) % E))) for i in range(E)]
    rng = np.random.RandomState(0)
    n_req = 12
    mixes = {
        "skewed": [pairs[0] if rng.random() < 0.8
                   else pairs[1 + rng.randint(len(pairs) - 1)]
                   for _ in range(n_req)],
        "uniform": [pairs[i % len(pairs)] for i in range(n_req)],
        "hot_repeat": [pairs[0]] * n_req,
    }
    prompts = [list(rng.randint(1, scfg.vocab - 1, 8)) for _ in range(n_req)]

    def run(paged, fps):
        eng = ServingEngine(scfg, sparams, dp=1, b_local=2, max_len=64,
                            prefix_sharing=False, mesh=None,
                            expert_paging=paged,
                            expert_budget=budget if paged else None)
        reqs = [Request(i, prompt=list(prompts[i]), max_new_tokens=8,
                        experts=fps[i]) for i in range(n_req)]
        for r in reqs:
            eng.submit(r)
        t0 = time.perf_counter()
        eng.run(max_steps=3000)
        dt = time.perf_counter() - t0
        assert eng.idle(), "mix never drained"
        return eng, [r.out_tokens for r in reqs], dt

    row = {"config": scfg.name, "expert_pages_full": full_pages,
           "expert_budget": budget, "mixes": {}}
    for mix, fps in mixes.items():
        _, want, _ = run(False, fps)
        eng, got, dt = run(True, fps)
        assert got == want, f"{mix}: paged streams diverged from resident"
        assert int(eng.telemetry.shard["moe_dropped_tokens"].sum()) == 0
        assert int(eng.telemetry.shard["expert_miss_pages_c2"].sum()) == 0
        peak = eng.stats["expert_pages_resident_peak"]
        saved = 1 - peak / max(full_pages, 1)
        eng.flush_experts()
        assert eng.leak_free(), f"{mix}: expert pages leaked"
        hr = eng.telemetry.expert_hit_rate()
        row["mixes"][mix] = {
            "expert_hit_rate": None if hr is None else round(hr, 4),
            "expert_load_pages": eng.stats["expert_load_pages"],
            "expert_evictions": eng.stats["expert_evictions"],
            "pages_resident_peak": peak,
            "weight_hbm_saved_frac": round(saved, 4),
            "sched_defer_experts": eng.stats["sched_defer_experts"],
            "token_identical": True,
            "wall_s": round(dt, 3),
        }
        print(f"serving_moe,0,mix={mix} hit_rate={hr} "
              f"peak_pages={peak}/{full_pages} "
              f"hbm_saved={saved:.0%} budget={budget} "
              f"evictions={eng.stats['expert_evictions']}")
    assert row["mixes"]["skewed"]["weight_hbm_saved_frac"] >= 0.30, (
        "skewed mix must save >= 30% peak weight HBM vs residency")
    assert (row["mixes"]["hot_repeat"]["expert_hit_rate"] or 0) >= \
        row["mixes"]["uniform"]["expert_hit_rate"], (
        "hot-repeat must hit at least as often as round-robin")
    return row


def spec_perf_smoke(cfg, params):
    """CI gate (spec-perf-smoke job): speculation must PAY.  Runs the
    shared baseline plus the gated partial-accept mix and asserts
    ``speedup_gen >= 1.0`` — the regression this PR exists to fix (the
    prior state of this path was 0.22x, ROADMAP §perf) — then profiles
    a window containing one speculative verify step with jax.profiler
    into ``spec_trace/`` for the job's artifact upload."""
    import jax
    import numpy as np
    from repro.serving.engine import Request, ServingEngine

    row = serving_speculative(cfg, params, smoke=True)
    part = row["partial_gated"]
    assert part["token_identical"], part
    assert part["leak_free"], part
    assert part["spec_lanes"] > 0, part
    assert part["speedup_gen"] >= 1.0, (
        f"speculation lost throughput on the partial-accept mix: {part}")

    # profiler trace of a speculative step: record + compile off-trace,
    # then step a hot replay until a draft lane fires inside the trace
    rng = np.random.RandomState(0)
    hot = list(rng.randint(1, 255, 16))
    uniq = [hot + list(rng.randint(1, 255, 4 + i)) for i in range(4)]
    eng = ServingEngine(cfg, params, dp=1, b_local=4, max_len=96,
                        chunk_size=16, speculate=True, draft_len=4)
    for w in range(2):
        for i, p in enumerate(uniq):
            eng.submit(Request(-1 - i - 100 * w, prompt=list(p),
                               max_new_tokens=8))
        eng.run(max_steps=500)
    for i, p in enumerate(uniq):
        eng.submit(Request(i, prompt=list(p), max_new_tokens=8))
    s0 = eng.stats["spec_lanes"]
    trace_dir = os.path.abspath("spec_trace")
    os.makedirs(trace_dir, exist_ok=True)
    with jax.profiler.trace(trace_dir):
        for _ in range(50):
            eng.step()
            if eng.stats["spec_lanes"] > s0:
                break
    assert eng.stats["spec_lanes"] > s0, "no speculative step in trace"
    eng.run(max_steps=500)
    assert eng.page_occupancy() == 0.0
    print(f"spec_perf_smoke,0,speedup_gen={part['speedup_gen']} "
          f"accept_rate={part['accept_rate']} "
          f"gate_skips={part['gate_skips']} trace_dir=spec_trace")


# serving benches runnable alone via --emit-json
_EMIT_JSON_FNS = {
    "mesh_shards": serving_mesh_shards,
    "speculative": serving_speculative,
    "moe": serving_moe,
}


def main(argv=None) -> None:
    import argparse

    from repro.launch.compile_cache import init_compile_cache
    init_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--emit-json", metavar="NAME", default=None,
                    choices=sorted(_EMIT_JSON_FNS),
                    help="run one serving bench and print its row as a "
                         f"'{_JSON_TAG}' line")
    ap.add_argument("--spec-smoke", action="store_true",
                    help="CI gate: assert gated partial-accept "
                         "speedup_gen >= 1.0 and write a jax.profiler "
                         "trace of a speculative step to spec_trace/")
    args = ap.parse_args(argv)
    if args.emit_json or args.spec_smoke:
        import jax
        from repro import models
        from repro.configs import get_config, smoke_config
        cfg = smoke_config(get_config("olmo-1b"))
        params = models.init_params(cfg, jax.random.PRNGKey(0))
        if args.spec_smoke:
            spec_perf_smoke(cfg, params)
            return
        row = _EMIT_JSON_FNS[args.emit_json](cfg, params)
        print(_JSON_TAG + json.dumps(row))
        return
    print("name,us_per_call,derived")
    result1_worst_case_steps()
    result1_vs_baselines()
    result1_space_overhead()
    result1_memory_blowup()
    result2_shared_op_cost()
    jax_block_pool_o1()
    jax_paged_kv_append()
    serving_throughput()


if __name__ == "__main__":
    main()
