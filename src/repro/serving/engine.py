"""Continuous-batching serving engine on the paper's allocator.

Two allocator integrations (DESIGN.md §2b):

* **host (faithful)**: admission runs through the wait-free
  :class:`~repro.core.allocator.WaitFreeAllocator` — sequence *slots*
  are the fixed-size blocks, scheduler lanes are the processes.  Each
  admission/release is O(1) regardless of fleet size, so request
  scheduling never stalls behind a global lock (the paper's claim,
  live in the control plane).
* **device (SPMD)**: KV pages come from the two-level
  :mod:`~repro.core.hier_pool` threaded through ``DecodeState`` — one
  private lane of capacity ``3*ell`` per serving slot, a shared pool
  per DP shard behind them, and one deamortized ``rebalance`` fused
  into the jitted step (off the per-token path), so per-step alloc and
  free touch only lane-local state: exactly the paper's structure at
  batch granularity.

Prefix sharing (DESIGN.md §7): a host-side radix trie over live
prompts (:mod:`.prefix_cache`) maps identical prompt prefixes from
concurrent requests onto the same physical pages.  Shared pages carry
an int16 refcount in the pool; a copy-on-write step at admission gives
each slot a private copy of the one partial page it will append into,
and release inside the jitted step decrements instead of frees.

Traffic-aware frontend (DESIGN.md §8): admission order, page-budget
backpressure, preemption, and pinned-prefix retention are policy owned
by :class:`~repro.serving.sched.AdmissionScheduler`; this engine owns
the mechanisms it drives — :meth:`admit`, :meth:`preempt`,
:meth:`evict_pin` and the pin table (whole pages of hot prefixes kept
alive after their request finishes, via cache-owned refcounts).

The token hot path is fully device-resident and runs ONE shape of
work: the variable-width **token-lane step** (DESIGN.md §10).  Each
active slot contributes a lane of tokens per step — a prefill chunk,
exactly one decode token (a width-1 lane), or one decode token plus
``k`` host-drafted speculative tokens — through the single jitted
``_serve_step``, which embeds the forward pass, per-request
temperature/top-k sampling (greedy by default, :mod:`.sampling`),
draft verification and whole-page rollback of rejected speculation
(``hier_pool.free_n_dp`` inside the jit), EOS/length done-detection,
and page release for finished slots, and returns a small packed status
array — the host performs EXACTLY ONE device→host sync per step
(``np.asarray(status)``).  Prefill lane widths come from the admission
scheduler's static chunk-bucket set (SLO-aware sizing: prefill shrinks
when latency-class work waits — :meth:`sched.AdmissionScheduler.
pick_chunk`); steady-state decode runs the same step at T=1 with the
previous token read from a device-resident register, never from the
host.  A step with nothing to feed skips the device entirely (idle
fast-path) and ``run`` exits as soon as both the batch and the
scheduler backlog are empty.  The pre-refactor single-token engine
path is gone — width-1 lanes ARE single-token decode.

Multi-host allocation plane (DESIGN.md §9): with >= dp devices the
engine builds a ``("dp",)`` mesh (``launch.mesh.make_dp_mesh``) and
shard_maps every jitted step — serve, release, share, pin, unpin —
over it, so each device owns exactly its shard's HierPool
leaves, lanes, refcounts, pin table, and KV pages; rebalance
drain/refill run entirely shard-local and the packed status row is the
only data crossing shards (one all_gather per step).  Admission is the
cross-host policy layer: the scheduler's per-shard committed/pinned
budgets are the mesh-visible state and prefix-trie donors are matched
strictly within a shard.  Without enough devices the same code runs
single-device vmap semantics, bit-identically.

Speculative decode on shared prefixes (DESIGN.md §10): the prefix
plane's :class:`~repro.serving.prefix_cache.SpeculationStore` records
the continuation history of hot (whole-page, often pinned) prompt
prefixes; the host drafts ``k`` tokens once per hot prefix per step
and the unified step scores each draft lane, accepts the matching
prefix, emits up to ``k + 1`` tokens, and rolls the rejected tail's
whole-page over-allocation back into the slot's private lane — still
one host sync and one collective per step.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import NULL, SimContext, WaitFreeAllocator, classed_pool, hier_pool
from ..core.classed_pool import CLS_EXPERT, CLS_KV, CLS_STATE
from ..launch.mesh import SERVE_DP_AXIS, make_dp_mesh
from ..launch.steps import (serve_register_pspec, serve_shardings,
                            serve_state_pspecs)
from ..models.decode_init import empty_decode_state, empty_serve_arrays
from ..models.layers import logits_apply, logits_argmax_chunked
from ..models.transformer import (EXPERT_PPE, DecodeState, expert_layer_slots,
                                  forward_decode_chunk, state_blocks_per_slot,
                                  state_page_tokens)
from ..runtime.fault import StepWatchdog
from .chaos import HostCrash, PoisonedRequest
from .expert_pages import (ExpertLedger, build_host_experts, expert_evict_step,
                           expert_load_step, expert_ref_step,
                           stub_expert_params)
from .prefix_cache import (PinnedPrefixes, PrefixCache, SpeculationStore,
                           pin_id_of, pin_prefix_step, share_pinned_step,
                           share_prefix_step, unpin_step)
from .sampling import sample_lane, sample_tokens
from .sched import Admission, AdmissionScheduler, SchedConfig
from .telemetry import (CTR_ALLOC, CTR_DRAIN, CTR_EDROP, CTR_EHIT, CTR_EMISS,
                        CTR_EPREF, CTR_FREED, CTR_MARGIN, CTR_REFILL,
                        CTR_ROLLBACK, CTR_SHARED_FREE, CTR_SPILL, N_CTR,
                        FlightRecorder, Telemetry)
from .trace import Tracer, scope


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int = 16
    # sampling (defaults reproduce the pre-sampler greedy engine)
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0
    # scheduling
    slo: str = "standard"
    # admitted expert footprint (MoE serving, DESIGN.md §15): the
    # experts this request may route to, enforced BEFORE top_k by the
    # router mask in both resident and expert-paged engines (so the two
    # are token-identical by construction).  None = all experts.
    experts: Optional[Tuple[int, ...]] = None
    # deadline: relative seconds from submit (0 = none); the engine
    # stamps the absolute ``deadline_at`` at first submission so the
    # deadline survives preemption, crash requeue, and warm restart
    deadline_s: float = 0.0
    deadline_at: float = 0.0
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    slot: Optional[int] = None
    done: bool = False
    rejected: Optional[str] = None     # typed failure reason, terminal
    preemptions: int = 0
    retries: int = 0                   # fault-retry attempts consumed
    submitted_at: float = 0.0
    first_token_at: float = 0.0
    finished_at: float = 0.0
    _seq: int = 0                      # admission order (victim choice)
    _spec_key: Optional[tuple] = None  # whole-page prefix (speculation)


def _release_slots(state: DecodeState, mask):
    """Jit-able: release all blocks of masked slots, zero their state.

    mask: bool[DP, Bl].  One :func:`hier_pool.free_n` per class per
    shard — each page loses one reference; pages still mapped by a
    prefix-sharing sibling or pinned by the prefix cache stay live
    (release decrements instead of frees), the rest return to the
    slot's lane / the shared pool.  In a two-class config the slot's
    CLS_STATE grants (``state_tables`` row) release the same way.

    Returns ``(state, spill)`` with spill int32[C, DP]: pages a full
    lane spilled straight to the shared stack during the frees — the
    term that keeps the §13 shared-free telescoping an equality
    (metered via :func:`hier_pool.free_n_metered`).
    """
    dp, bl, maxp = state.page_tables.shape
    C = len(state.pool.classes)
    to_free = jnp.where(mask[:, :, None], state.page_tables, NULL)
    pool, spill_kv = classed_pool.free_n_metered_dp(state.pool, CLS_KV,
                                                    to_free)
    spills = [spill_kv] + [jnp.zeros_like(spill_kv)] * (C - 1)
    if state.state_tables is not None and C > CLS_STATE:
        st_free = jnp.where(mask[:, :, None], state.state_tables, NULL)
        pool, spills[CLS_STATE] = classed_pool.free_n_metered_dp(
            pool, CLS_STATE, st_free)
        state = state._replace(state_tables=jnp.where(
            mask[:, :, None], NULL, state.state_tables))
    page_tables = jnp.where(mask[:, :, None], NULL, state.page_tables)
    seq_lens = jnp.where(mask, 0, state.seq_lens)

    def zero_masked(tree):
        def f(a):
            if a.ndim >= 3 and a.shape[1] == dp and a.shape[2] == bl:
                m = mask.reshape((1, dp, bl) + (1,) * (a.ndim - 3))
                return jnp.where(m, jnp.zeros_like(a), a)
            return a
        return jax.tree.map(f, tree)

    rings = zero_masked(state.rings)
    rec = zero_masked(state.rec)
    state = state._replace(page_tables=page_tables, seq_lens=seq_lens,
                           pool=pool, rings=rings, rec=rec)
    return state, jnp.stack(spills)


def _alloc_state_step(state: DecodeState, counts):
    """Jit-able admission-time CLS_STATE grant: pull ``counts[d, b]``
    fine blocks per masked slot from class 1's shared stack (bulk
    admission, like prefill loading — off the serve step's hot path)
    and write them into the slot's ``state_tables`` row.  §4.2 for this
    class: lanes hold at most their 3*ell*L slack, so while admission
    respects the class budget the shared stack covers every grant."""
    kmax = state.state_tables.shape[2]
    pool, ids = classed_pool.alloc_from_shared_dp(
        state.pool, CLS_STATE, counts, kmax)
    tables = jnp.where(counts[:, :, None] > 0, ids, state.state_tables)
    return state._replace(pool=pool, state_tables=tables)


# Packed per-step status (the step's single device->host transfer),
# int32[T + 3 + C*N_CTR, DP, Bl] for a width-T step over C size
# classes: rows [0, T) carry each slot's emitted tokens this step in
# order (-1 padding — one row per lane position, so a fully-accepted
# draft lane reports k + 1 tokens in the same single sync), then three
# bookkeeping rows addressed relative to T:
STATUS_EMITTED = 0   # + T: emitted-token count this step
STATUS_DONE = 1      # + T: 1 iff the slot finished (pages released)
STATUS_PAGES = 2     # + T: KV pages-in-use on the slot's DP shard
# followed by C class-major blocks of the N_CTR telemetry counter rows
# (telemetry.CTR_* order within a block, class c's block at offset
# T + 3 + c*N_CTR; per-shard values broadcast over Bl like the PAGES
# row): allocator events metered INSIDE the jit from pool free-level
# deltas the step already computes, harvested through the same single
# sync and the same single all_gather — the DESIGN.md §13
# zero-extra-sync argument, unchanged by the class axis.


def _serve_step(cfg, max_len, eos_id, use_sampler, spec, n_verify, axis_name,
                params, state, last_tok, out_count, budget, temps, topks,
                seeds, prompt_toks, feed_lens, is_prompt, emit, expert_mask):
    """One fully device-resident token-lane step (jitted per lane width
    T x the two static feature flags).

    prompt_toks: int32[DP, Bl, T] host-provided lane tokens.  A prompt
    lane is a prompt chunk; a generating lane reads its first token
    from the device-resident ``last_tok`` register and — when ``spec``
    — carries host-drafted speculative tokens at positions 1..k.
    feed_lens: tokens fed per slot this step (0 = idle; 1 = plain
    decode, the width-1 lane; >1 with is_prompt False = draft+verify
    lane); is_prompt: slot consumes prompt tokens; emit: slot may emit
    output this step (host knows this statically — "prompt exhausted by
    this chunk" or "generating").  temps/topks/seeds: per-slot sampling
    registers, written at admission like ``budget`` (temp <= 0 →
    greedy; see sampling.py for the (seed, out_count) keying that makes
    preemption — and speculation — invisible in sampled output).

    ``use_sampler`` and ``spec`` are STATIC: the host knows at dispatch
    whether any active request samples and whether any lane carries
    drafts, so the default all-greedy non-speculative variant compiles
    without the sampler's full-vocab sort + Gumbel draw and without the
    per-position logits of draft verification — plain serving pays
    nothing for either feature.

    Speculative verify+rollback (``spec``; DESIGN.md §10, §12): the
    ``n_verify`` (static: draft_len + 1) verify positions of each lane
    are gathered and scored — never the full lane width — attention
    runs through the page-grouped verify kernel, position
    i's candidate is sampled with key index ``out_count + i``, and a
    draft is accepted iff it equals the previous position's candidate —
    so an accepted stream is exactly the stream sequential decode would
    have produced, key-for-key.  The slot emits its accepted prefix
    plus one verify token (1..k+1 tokens), keeps exactly that many KV
    positions, and returns the whole-page over-allocation of the
    rejected tail to its own private lane via :func:`hier_pool.
    free_n_dp` — inside this jit, before the rebalance, so §4.2 sees a
    lane at least as stocked as a non-speculative step would leave it.

    Folds sampling, verification/rollback, EOS/budget/length
    done-detection, page release for finished slots, and the
    once-per-step :func:`hier_pool.rebalance` (the paper's deamortized
    shared-pool traffic, off the per-token path) into the step so the
    host syncs exactly once, on the returned packed status int32[T+3,
    DP, Bl] (see STATUS_* row offsets; the PAGES row carries per-shard
    pages-in-use so occupancy tracking — and the scheduler's high-water
    pin eviction — costs no extra transfer).

    ``axis_name`` is STATIC: set (to the mesh axis) when the step runs
    under shard_map on the multi-device allocation plane (DESIGN.md
    §9).  Everything above — forward pass, page alloc/free, draft
    verification and rollback, rebalance drain/refill, sampling,
    done-detection — is then device-local by construction (each device
    owns its shard's HierPool leaves, lanes, refcounts, and KV pages);
    the ONE collective per step is the all_gather that replicates the
    packed status row so every host drives admission from the same
    global view.
    """
    DP, Bl, T = prompt_toks.shape
    C = len(state.pool.classes)
    gen_lane = prompt_toks.at[:, :, 0].set(last_tok)
    toks = jnp.where(is_prompt[..., None], prompt_toks, gen_lane)
    active = feed_lens > 0
    base = state.seq_lens
    # telemetry counter block (DESIGN.md §13, class axis §14):
    # allocator events are metered from per-class per-shard pool
    # free-level deltas between the step's existing phases — pure
    # arithmetic on values the step already holds, no extra device work
    # beyond a few scalar subtractions per class
    def free_all(pool):
        return [classed_pool.free_per_shard(pool, c)      # C x int32[DP]
                for c in range(C)]

    free_in = free_all(state.pool)

    # ``expert_mask`` (bool[DP, Bl, E]) is a per-slot register like
    # temps/seeds: each slot's admitted expert footprint, applied
    # before top_k at every MoE layer.  All-True rows are bit-identical
    # to no mask, so non-MoE and unrestricted slots pay nothing.  The
    # forward's ``fwd_meta`` meters (capacity drops, expert page
    # hit/miss/prefetch) ride the counter block below — same single
    # sync, same single collective (DESIGN.md §15).
    emask = expert_mask if cfg.moe is not None else None
    hidden, state, fwd_meta = forward_decode_chunk(
        cfg, params, toks, state, feed_lens, active=active, verify=spec,
        expert_mask=emask)
    free_fwd = free_all(state.pool)
    # forward only allocates, and only in the KV class
    ctr_alloc = [free_in[c] - free_fwd[c] for c in range(C)]
    idx = jnp.maximum(feed_lens - 1, 0)
    emit = emit & active
    # projection, sampling, verification (the step's ``head`` scope)
    with scope("head"):
        if spec:
            # --- projection slimming (DESIGN.md §12): only the k + 1
            # verify positions of a draft lane need logits.  Gather those
            # hidden rows FIRST — a generating lane's verify positions are
            # lane positions 0..Tv-1, a prompt lane needs only its single
            # emitting position idx (broadcast over the gathered rows) —
            # then project the [DP, Bl, Tv, d] gather instead of the whole
            # [DP, Bl, T, d] lane, so a draft riding a chunk-width step
            # pays k + 1 vocab columns per slot, never T
            Tv = min(T, n_verify) if n_verify > 0 else T
            j = jnp.arange(Tv, dtype=jnp.int32)
            vpos = jnp.where(is_prompt[..., None], idx[..., None],
                             jnp.minimum(j, T - 1)[None, None])  # [DP,Bl,Tv]
            hidden_v = jnp.take_along_axis(hidden, vpos[..., None], axis=2)
            # output-key index per position: generating lanes emit from
            # position 0 on (key out_count + i); a prompt lane's single
            # emitting position is output index 0 (key out_count) — the
            # gather changes WHICH rows are scored, never the key a given
            # output index draws with, so the fold_in(seed, out_count + i)
            # stream stays bit-exact
            cnt = out_count[..., None] + jnp.where(is_prompt[..., None], 0,
                                                   j[None, None])
            if use_sampler:
                logits = logits_apply(cfg, params["embed"],
                                      hidden_v)            # [DP,Bl,Tv,V]
                nxt_all = sample_lane(logits, temps, topks, seeds, cnt)
            else:
                # chunked-vocab argmax: greedy verification never builds
                # the [Tv, V] tensor either
                nxt_all = logits_argmax_chunked(cfg, params["embed"], hidden_v)
            # a prompt lane's gathered rows all hold position idx, so row 0
            # is its emitting candidate; a generating lane's last fed
            # position is row feed_lens - 1 (feed <= Tv by dispatch)
            vidx = jnp.where(is_prompt, 0, jnp.minimum(idx, Tv - 1))
            last_pos = jnp.take_along_axis(nxt_all, vidx[..., None],
                                           axis=2)[..., 0]
            # emission stream: generating lanes emit candidates in lane
            # order; prompt lanes emit (at most) their last position's
            etoks = jnp.where(is_prompt[..., None], last_pos[..., None],
                              nxt_all)
            # draft i (lane position i >= 1) is accepted iff position i-1's
            # candidate equals it and every earlier draft was accepted
            dmatch = ((nxt_all[..., :-1] == toks[..., 1:Tv]) &
                      (j[None, None, 1:] < feed_lens[..., None]))
            accepted = jnp.sum(jnp.cumprod(dmatch.astype(jnp.int32), axis=-1),
                               axis=-1)
            n_cand = (jnp.where(is_prompt, 1, accepted + 1)
                      * emit.astype(jnp.int32))
            # EOS / budget truncate the emission stream (an emitted EOS is
            # included, then the slot finishes)
            is_e = (etoks == eos_id) & (j[None, None] < n_cand[..., None])
            eos_cut = jnp.where(jnp.any(is_e, axis=-1),
                                jnp.argmax(is_e, axis=-1) + 1, Tv + 1)
            room = jnp.maximum(budget - out_count, 0)
            n_emit = jnp.minimum(n_cand, jnp.minimum(room, eos_cut))
            hit_eos = jnp.any(is_e & (j[None, None] < n_emit[..., None]),
                              axis=-1)
            # --- rollback: keep last_tok + accepted drafts, free the
            # rejected tail's whole-page over-allocation back to the slot's
            # OWN lane (same-shard by construction; refcount 1 pages —
            # granted this very step — so free_n restacks them)
            adv = state.seq_lens - base
            n_keep = jnp.where(is_prompt, adv, jnp.minimum(n_emit, adv))
            psz = cfg.page_size
            maxp = state.page_tables.shape[2]
            keep_pages = (base + n_keep + psz - 1) // psz
            have_pages = (base + adv + psz - 1) // psz
            kidx = jnp.arange(maxp, dtype=jnp.int32)[None, None, :]
            roll = ((kidx >= keep_pages[..., None]) &
                    (kidx < have_pages[..., None]))
            pool, spill_roll = classed_pool.free_n_metered_dp(
                state.pool, CLS_KV, jnp.where(roll, state.page_tables, NULL))
            state = state._replace(
                pool=pool,
                page_tables=jnp.where(roll, NULL, state.page_tables),
                seq_lens=base + n_keep)
            # rollback pages are refcount-1 by construction (granted this
            # very step), so the free-level delta counts them exactly;
            # rollback is KV-class traffic only
            ctr_roll = [classed_pool.free_per_shard(state.pool, CLS_KV)
                        - free_fwd[CLS_KV]]
            ctr_roll += [jnp.zeros_like(ctr_roll[0]) for _ in range(C - 1)]
            out_count = out_count + n_emit
            seq_full = state.seq_lens >= max_len - 1
            done = active & ((out_count >= budget) | seq_full | hit_eos)
            last_emitted = jnp.take_along_axis(
                etoks, jnp.maximum(n_emit - 1, 0)[..., None], axis=2)[..., 0]
            last_tok = jnp.where(n_emit > 0, last_emitted, last_tok)
            tok_rows = jnp.where(j[None, None] < n_emit[..., None], etoks, -1)
            if Tv < T:      # pad the gathered rows back to the lane width
                tok_rows = jnp.concatenate(
                    [tok_rows, jnp.full((DP, Bl, T - Tv), -1, jnp.int32)],
                    axis=-1)
        else:
            # no drafts, no rollback
            ctr_roll = [jnp.zeros_like(f) for f in free_in]
            spill_roll = jnp.zeros_like(free_in[CLS_KV])
            h_last = jnp.take_along_axis(hidden, idx[..., None, None],
                                         axis=2)[:, :, 0]     # [DP, Bl, d]
            logits = logits_apply(cfg, params["embed"], h_last)
            if use_sampler:
                nxt = sample_tokens(logits, temps, topks, seeds, out_count)
            else:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out_count = out_count + emit.astype(jnp.int32)
            seq_full = state.seq_lens >= max_len - 1
            done = active & ((out_count >= budget) | seq_full |
                             (emit & (nxt == eos_id)))
            last_tok = jnp.where(emit, nxt, last_tok)
            n_emit = emit.astype(jnp.int32)
            tok_rows = jnp.concatenate(
                [jnp.where(emit, nxt, -1)[..., None],
                 jnp.full((DP, Bl, T - 1), -1, jnp.int32)], axis=-1)
    state, spill_rel = _release_slots(state, done)
    # everything freed since the forward pass actually returned free —
    # spec rollback plus finished-slot release (shared/pinned pages a
    # sibling still maps only decrement, and correctly don't count)
    ctr_freed = [classed_pool.free_per_shard(state.pool, c) - free_fwd[c]
                 for c in range(C)]
    # pages a full lane spilled straight to the shared stack across
    # ALL of this step's frees — the explicit term that keeps the §13
    # shared-free telescoping an equality instead of an inequality:
    # shared_top' - shared_top == spill - drain_net per step
    ctr_spill = [spill_rel[c] + (spill_roll if c == CLS_KV else 0)
                 for c in range(C)]
    # deamortized shared<->lane traffic: once per step, off the
    # per-token path (the paper's run_delayed_step), fused over all
    # classes.  Phases run separately (== rebalance_dp by definition)
    # so the counter block meters per-class drain and refill traffic
    # from the lane-stock deltas.
    lane0 = [jnp.sum(state.pool.classes[c].private_top, axis=-1)
             for c in range(C)]
    pool = classed_pool.rebalance_drain_dp(state.pool)
    lane_drained = [jnp.sum(pool.classes[c].private_top, axis=-1)
                    for c in range(C)]
    pool = classed_pool.rebalance_refill_dp(pool)
    state = state._replace(pool=pool)
    ctr_drain = [lane0[c] - lane_drained[c] for c in range(C)]
    ctr_refill = [jnp.sum(pool.classes[c].private_top, axis=-1)
                  - lane_drained[c] for c in range(C)]

    # the PAGES status row stays the coarse KV class — the scheduler's
    # page budget and high-water pin eviction are KV-page quantities
    kv = state.pool.classes[CLS_KV]
    pages_local = kv.shared.free_ids.shape[1]
    free_now = kv.shared.top + jnp.sum(kv.private_top, axis=1)
    pages_used = (pages_local - free_now).astype(jnp.int32)      # [DP]
    # per-class post-rebalance invariant gauges: each class's shared
    # free level (host min-accumulates the low-water mark) and its
    # §4.2 never-dry margin min(private_top) - ell (>= 0 iff held)
    ctrs = []
    zero_dp = jnp.zeros((DP,), jnp.int32)
    for c in range(C):
        hp = state.pool.classes[c]
        margin = jnp.min(hp.private_top, axis=-1) - hier_pool.lane_ell(hp)
        ctr = jnp.empty((N_CTR, DP), jnp.int32)
        ctr = ctr.at[CTR_ALLOC].set(ctr_alloc[c])
        ctr = ctr.at[CTR_FREED].set(ctr_freed[c])
        ctr = ctr.at[CTR_ROLLBACK].set(ctr_roll[c])
        ctr = ctr.at[CTR_DRAIN].set(ctr_drain[c])
        ctr = ctr.at[CTR_REFILL].set(ctr_refill[c])
        ctr = ctr.at[CTR_SPILL].set(ctr_spill[c])
        ctr = ctr.at[CTR_SHARED_FREE].set(hp.shared.top)
        ctr = ctr.at[CTR_MARGIN].set(margin)
        # §15 expert-paging meters: page traffic rides the expert
        # class's block (``_c2`` keys), capacity drops ride class 0 so
        # resident-weight MoE engines meter them too
        ctr = ctr.at[CTR_EHIT].set(
            fwd_meta["expert_hit_pages"] if c == CLS_EXPERT else zero_dp)
        ctr = ctr.at[CTR_EMISS].set(
            fwd_meta["expert_miss_pages"] if c == CLS_EXPERT else zero_dp)
        ctr = ctr.at[CTR_EPREF].set(
            fwd_meta["expert_prefetch_pages"] if c == CLS_EXPERT
            else zero_dp)
        ctr = ctr.at[CTR_EDROP].set(
            fwd_meta["moe_dropped"] if c == 0 else zero_dp)
        ctrs.append(ctr)
    ctr = jnp.concatenate(ctrs)                  # [C * N_CTR, DP]
    status = jnp.concatenate(
        [tok_rows.transpose(2, 0, 1),
         n_emit[None],
         done.astype(jnp.int32)[None],
         jnp.broadcast_to(pages_used[:, None], (DP, Bl))[None],
         jnp.broadcast_to(ctr[:, :, None], (C * N_CTR, DP, Bl))])
    if axis_name is not None:
        # the step's single collective: only the packed status row
        # crosses shards (DESIGN.md §9 one-sync argument)
        status = jax.lax.all_gather(status, axis_name, axis=1, tiled=True)
    return state, last_tok, out_count, status


def step_collectives(hlo: str) -> Tuple[int, int]:
    """(all-gathers, other collectives) in compiled HLO text.  A serve
    step on the dp mesh must show (1, 0): the packed status all_gather
    is its one collective (DESIGN.md §9)."""
    n_gather = hlo.count("all-gather(") + hlo.count("all-gather-start(")
    n_other = sum(hlo.count(c) for c in
                  ("all-reduce(", "all-reduce-start(", "all-to-all(",
                   "collective-permute(", "collective-permute-start("))
    return n_gather, n_other


class ServingEngine:
    def __init__(self, cfg, params, dp: int = 1, b_local: int = 4,
                 max_len: int = 512, scheduler_lanes: int = 2,
                 greedy: bool = True, chunk_size: int = 8,
                 eos_id: Optional[int] = None,
                 prefix_sharing: bool = True,
                 speculate: bool = False, draft_len: int = 4,
                 spec_gate: bool = True,
                 sched: Optional[SchedConfig] = None,
                 mesh="auto",
                 size_classes: int = 1, degraded_pool_ok: bool = False,
                 expert_paging: bool = False,
                 expert_budget: Optional[int] = None,
                 journal=None, injector=None,
                 watchdog: Optional[StepWatchdog] = None,
                 clock=None, max_restarts: int = 0,
                 telemetry: Optional[Telemetry] = None,
                 tracer: Optional[Tracer] = None,
                 flight: Optional[FlightRecorder] = None):
        self.cfg = cfg
        self.params = params
        self.dp, self.bl = dp, b_local
        # expert-paged MoE serving (DESIGN.md §15): route expert FFN
        # weights through the classed pool's third size class.  Gated
        # on an actual MoE config with paged layer slots; the class
        # vector widens to three BEFORE telemetry/pool construction so
        # every downstream n_classes consumer (counter blocks, specs,
        # max_live) sees the expert class.
        self._elayer_n = expert_layer_slots(cfg)
        self.expert_paging = bool(
            (expert_paging or int(size_classes) > CLS_EXPERT)
            and cfg.moe is not None and self._elayer_n > 0)
        if self.expert_paging:
            size_classes = max(int(size_classes), CLS_EXPERT + 1)
        else:
            # the expert class is meaningless without paged MoE slots
            size_classes = min(int(size_classes), CLS_EXPERT)
        # observability plane (DESIGN.md §13): ONE facade every
        # subsystem emits through.  engine.stats stays a live property
        # view of telemetry.counters, so pre-§13 callers (and the
        # benches) read the same ledger the typed counters write.
        if telemetry is None:
            telemetry = Telemetry(dp, tracer=tracer, flight=flight,
                                  n_classes=max(int(size_classes), 1))
        self.telemetry = telemetry
        self.tracer = telemetry.tracer
        if telemetry.flight is None:
            telemetry.flight = FlightRecorder()
        self.flight = telemetry.flight
        self.max_len = max_len
        self.chunk = max(int(chunk_size), 1)
        self.draft_len = max(int(draft_len), 0)
        # lanes must cover the widest lane the engine will ever
        # dispatch: the prefill chunk or a draft+verify lane (§4.2's
        # ell >= max per-step demand, by construction)
        lane_tokens = self.chunk
        if speculate:
            lane_tokens = max(lane_tokens, self.draft_len + 1)
        # multi-host allocation plane (DESIGN.md §9): with >= dp devices
        # the engine owns a ("dp",) mesh, shards every DecodeState leaf
        # and per-slot register over it, and shard_maps the jitted steps
        # so each device holds exactly its shard's pool/lanes/refcounts/
        # pin-table/KV pages.  mesh=None (or too few devices) keeps the
        # single-device vmap semantics — bit-identical outputs.
        if mesh == "auto":
            mesh = make_dp_mesh(dp)
        self.mesh: Optional[Mesh] = mesh
        self._axis = SERVE_DP_AXIS if mesh is not None else None
        # CLS_EXPERT page budget: the cache capacity the host ledger
        # enforces per shard (full residency when unset — paging then
        # costs nothing and misses only on first touch).  The pool
        # itself is provisioned at budget + lane stock (pool_class_
        # specs), so admission respecting the budget keeps every bulk
        # grant inside the §4.2 slack.
        self.expert_budget = 0
        if self.expert_paging:
            assert dp == 1 or self.mesh is not None, (
                "expert paging needs shard-local DP == 1: run dp=1 or "
                "give each shard its own device (mesh='auto')")
            full = self._elayer_n * cfg.moe.num_experts * EXPERT_PPE
            self.expert_budget = int(
                expert_budget
                or (sched.expert_budget if sched is not None else 0)
                or full)
        self.state = empty_decode_state(
            cfg, dp, b_local, max_len, chunk=lane_tokens,
            size_classes=size_classes,
            expert_budget=(self.expert_budget if self.expert_paging
                           else None))
        self.n_classes = len(self.state.pool.classes)
        assert self.telemetry.n_classes == self.n_classes, (
            "telemetry n_classes must match the engine's size-class "
            "vector (pass Telemetry(dp, n_classes=...))")
        self._pspecs = serve_state_pspecs(self.state)
        self._rspec = serve_register_pspec()
        if self.mesh is not None:
            self.state = jax.device_put(
                self.state, serve_shardings(self.mesh, self._pspecs))
        self.last_tok, self.out_count, self.budget = \
            empty_serve_arrays(dp, b_local)
        # per-slot sampling registers (written at admission, read by the
        # jitted step; all-zeros == greedy everywhere)
        self.temps = jnp.zeros((dp, b_local), jnp.float32)
        self.topks = jnp.zeros((dp, b_local), jnp.int32)
        self.seeds = jnp.zeros((dp, b_local), jnp.int32)
        # per-slot admitted expert footprint (bool[DP, Bl, E]) — a
        # register like temps/seeds, applied pre-top_k in the jitted
        # step.  All-True (the reset value) is bit-identical to no mask,
        # so non-MoE and unrestricted requests pay nothing.
        E_reg = cfg.moe.num_experts if cfg.moe is not None else 1
        self.expert_mask = jnp.ones((dp, b_local, E_reg), bool)
        if self.mesh is not None:
            reg_ns = NamedSharding(self.mesh, self._rspec)
            (self.last_tok, self.out_count, self.budget, self.temps,
             self.topks, self.seeds, self.expert_mask) = jax.device_put(
                (self.last_tok, self.out_count, self.budget, self.temps,
                 self.topks, self.seeds, self.expert_mask), reg_ns)
        self.greedy = greedy
        # sequences can never outgrow the page table (maxp * psz tokens,
        # < max_len when max_len is not a page multiple); done-detection
        # and feed capping use the effective capacity so a chunk is never
        # submitted that forward_decode_chunk would have to reject
        maxp = self.state.page_tables.shape[2]
        self.capacity = (min(max_len, maxp * cfg.page_size)
                         if self.state.kv_pages else max_len)
        self.pages_local = classed_pool.pages_local(self.state.pool, CLS_KV)
        # CLS_STATE blocks one slot's bounded state occupies (0 in a
        # single-class config, and 0 for a fully-paged model even when
        # the class exists): granted at admission, freed at release
        self._state_blocks = (state_blocks_per_slot(cfg, max_len)
                              if self.state.state_tables is not None else 0)
        # plan-time §4.2 validation (DESIGN.md §14): every class must
        # carry pool-wide slack 3*ell*L over its worst-case live blocks
        # — hier_pool.create's own per-lane assert is NOT sufficient
        # (a config can satisfy it and still run a lane dry between
        # rebalances).  ``degraded_pool_ok`` documents the fallback:
        # under-provisioned classes keep serving correctly through the
        # synchronous alloc_n_or_shared shared-pool path, but the O(1)
        # lane-local guarantee (and the never-dry margin gauge) is
        # forfeit for that class.
        max_live = [b_local * maxp] + [b_local * self._state_blocks] * (
            self.n_classes - 1)
        if self.n_classes > CLS_EXPERT:
            # worst-case live CLS_EXPERT pages == the admission budget
            # (the host ledger never loads past it; DESIGN.md §15), so
            # the class's §4.2 slack is exactly its lane stock
            max_live[CLS_EXPERT] = self.expert_budget
        specs = tuple(
            classed_pool.ClassSpec(
                page_size=(cfg.page_size if c == CLS_KV
                           else state_page_tokens(cfg)),
                num_blocks=hp.shared.free_ids.shape[-1],
                num_lanes=hp.private_top.shape[-1],
                ell=hp.private_ids.shape[-1] // 3)
            for c, hp in enumerate(self.state.pool.classes))
        self.pool_provisioned = classed_pool.validate_specs(
            specs, max_live, degraded_ok=degraded_pool_ok)
        self._fed: Dict[int, int] = {}       # host shadow of seq_lens

        # fused device-resident token-lane step, compiled once per lane
        # width T (the scheduler's static chunk buckets, the draft lane
        # width, and T=1) times the two static feature flags
        # (use_sampler, spec) — all-greedy non-speculative batches, the
        # default, never compile or pay for either feature.  On the
        # mesh plane every jitted step is shard_mapped over the ("dp",)
        # axis — shard-locality is enforced structurally, not just by
        # the vmap convention (DESIGN.md §9).
        S, R = self._pspecs, self._rspec

        def wrap(fn, in_specs, out_specs, donate=()):
            if self.mesh is None:
                return jax.jit(fn, donate_argnums=donate)
            return jax.jit(
                jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                              out_specs=out_specs, check_vma=False),
                donate_argnums=donate)

        eos = -1 if eos_id is None else int(eos_id)
        self.eos_id = eos_id
        self._spec_T = self.draft_len + 1
        def serve_step(sampler, spec):
            # named, so that its module reads ``jit_serve_step`` and its
            # ops' ``tf_op`` ``jit(serve_step)/...`` in a profile
            fn = functools.partial(_serve_step, cfg, self.capacity, eos,
                                   sampler, spec, self._spec_T, self._axis)
            fn.__name__ = "serve_step"
            return fn

        self._serve_variants = {
            (sampler, spec): wrap(
                serve_step(sampler, spec),
                in_specs=(P(), S) + (R,) * 11,
                out_specs=(S, R, R, P()),
                donate=(1, 2, 3))
            for sampler in (False, True) for spec in (False, True)}
        self._sampling_slots: set = set()
        # (T, spec, use_sampler) of every serve-step variant dispatched:
        # what :meth:`compiled_step_text` is asked about by checks that
        # a compiled step holds its kernels and one collective
        self.step_variants: set = set()
        self._release = wrap(_release_slots, in_specs=(S, R),
                             out_specs=(S, P(None, "dp")), donate=(0,))
        # admission-time CLS_STATE grant (two-class configs): a jitted
        # bulk shared-pool pull, same off-hot-path shape as prefill
        # loading and the share/pin steps
        self._alloc_state = None
        if self.state.state_tables is not None:
            self._alloc_state = wrap(_alloc_state_step, in_specs=(S, R),
                                     out_specs=S, donate=(0,))

        # expert-paged weight plane (DESIGN.md §15): keep the full
        # expert stacks on HOST, stub the device param leaves to [..,1,1]
        # placeholders (the HBM the paging buys), and manage device
        # residency through CLS_EXPERT pages + the host ledger.  Load /
        # evict / refcount steps are admission-time traffic — jitted,
        # but never inside _serve_step, so the one-sync/one-collective
        # step shape is untouched.
        self.expert_ledger: Optional[ExpertLedger] = None
        self._host_experts = None
        self._slot_experts: Dict[int, tuple] = {}
        self._elayer_slots: List[Tuple[str, int]] = []
        self._fp_masks: Dict[tuple, Any] = {}
        if self.expert_paging:
            self._host_experts = build_host_experts(cfg, params)
            self.params = params = stub_expert_params(params)
            self.expert_ledger = ExpertLedger(dp, self.expert_budget)
            self._elayer_slots = [
                (pos, g)
                for pos in sorted(self.state.expert_tables)
                for g in range(self.state.expert_tables[pos].shape[0])]
            self._eload = {
                pos: wrap(functools.partial(expert_load_step, pos),
                          in_specs=(S, R, P(), P("dp"), P(), P()),
                          out_specs=S, donate=(0,))
                for pos in self.state.expert_tables}
            self._eevict = {
                pos: wrap(functools.partial(expert_evict_step, pos),
                          in_specs=(S, P("dp"), P(), P()),
                          out_specs=S, donate=(0,))
                for pos in self.state.expert_tables}
            self._eref = {
                free: wrap(functools.partial(expert_ref_step, free),
                           in_specs=(S, P(), P("dp")),
                           out_specs=S, donate=(0,))
                for free in (False, True)}

        if self.mesh is not None:
            # replicate the weights once: a param tree left on one
            # device would be broadcast to every mesh device each step
            self.params = jax.device_put(self.params,
                                         NamedSharding(self.mesh, P()))

        # prefix sharing: only sound when the whole decode state is
        # paged (ring / recurrent layers would need donor state at the
        # match point); page ids are shard-local, so matches are too
        self.prefix_cache: Optional[PrefixCache] = None
        if (prefix_sharing and self.state.kv_pages
                and not self.state.rings and not self.state.rec
                and self.state.enc_kv is None):
            self.prefix_cache = PrefixCache(cfg.page_size)
            self._share = wrap(
                functools.partial(share_prefix_step, cfg.page_size,
                                  axis_name=self._axis),
                in_specs=(S, R, R, P()), out_specs=(S, P()),
                donate=(0,))

        # speculative decode on shared prefixes (DESIGN.md §10): sound
        # under the same fully-paged condition — rejected drafts roll
        # back pages and seq_lens, but ring/recurrent state cannot be
        # un-evolved, so those models never dispatch the spec variant
        self.spec_store: Optional[SpeculationStore] = None
        if speculate and self.draft_len > 0 and self.prefix_cache is not None:
            self.spec_store = SpeculationStore(cfg.page_size)
        self.speculate = self.spec_store is not None
        # accept-rate-gated drafting (DESIGN.md §12): per-prefix EWMA
        # accept rate (lives in the SpeculationStore, so it survives
        # warm restarts with the streams) against a measured per-step
        # cost model — an EWMA of step wall time keyed by (lane width,
        # spec).  Before both sides are measured, the break-even test
        # falls back to a linear cost model: a width-(k+1) verify step
        # costs ~ (1 + slope * k) plain decode steps (the slope is what
        # the verify kernel + projection slimming shrink).
        self.spec_gate = bool(spec_gate)
        self.spec_cost_slope = 0.25
        self._step_cost: Dict[Tuple[int, bool], float] = {}
        self._cost_seen: set = set()

        # traffic-aware frontend: admission order / page budgets /
        # preemption / pin policy (DESIGN.md §8).  The default budget is
        # b_local * maxp — the table capacity the pool is provisioned
        # for — so with pinning off the scheduler admits exactly what
        # the pre-scheduler engine admitted.
        self.sched_config = sched or SchedConfig()
        self.scheduler = AdmissionScheduler(
            self.sched_config, n_shards=dp, page_budget=b_local * maxp,
            state_budget=b_local * self._state_blocks)

        # pinned prefixes: device pin table (rows of cache-owned page
        # ids per shard) + host LRU ledger; disabled unless the sched
        # config grants a pin budget AND the model can share at all
        self.pins: Optional[PinnedPrefixes] = None
        self.pin_tables: Optional[jax.Array] = None
        if self.prefix_cache is not None and self.sched_config.pin_pages > 0:
            self.pins = PinnedPrefixes(dp, self.sched_config.pin_rows,
                                       self.sched_config.pin_pages)
            self.pin_tables = jnp.full(
                (dp, self.sched_config.pin_rows, maxp), -1, jnp.int32)
            if self.mesh is not None:
                # pin rows are shard-owned like everything else: a pin
                # on shard d references only shard-d pages, and its
                # addref/free traffic stays on shard d's device
                self.pin_tables = jax.device_put(
                    self.pin_tables, NamedSharding(self.mesh, R))
            PS = self._pspecs.pool
            self._pin = wrap(pin_prefix_step,
                             in_specs=(PS, R, R, R, R, P()),
                             out_specs=(PS, R), donate=(0, 1))
            self._unpin = wrap(unpin_step, in_specs=(PS, R, R),
                               out_specs=(PS, R), donate=(0, 1))
            self._share_pinned = wrap(
                functools.partial(share_pinned_step, cfg.page_size,
                                  axis_name=self._axis),
                in_specs=(S, R, R, R, P()), out_specs=(S, P()),
                donate=(0,))
        self._pinned_slots: set = set()
        # host copy of the status PAGES row (per-shard pages-in-use,
        # refreshed by the step's single sync; drives high-water pin
        # eviction without any extra transfer) + per-shard occupancy
        # accumulators for the mesh bench (shard_occupancy())
        self.pages_used_shard: List[int] = [0] * dp
        self._pages_shard_sum = np.zeros(dp, np.int64)
        self._pages_shard_peak = np.zeros(dp, np.int64)

        # host-side wait-free slot allocator: slots are fixed-size blocks.
        n_slots = dp * b_local
        self.lane_ctx = SimContext(scheduler_lanes, seed=0)
        self.slot_alloc = WaitFreeAllocator(
            self.lane_ctx, ell=max(3 * scheduler_lanes, 4),
            shared_batches=max(2, n_slots), allow_os_growth=True)
        # bind allocator block ids <-> engine slots (first n_slots blocks)
        self._slot_of_block: Dict[int, int] = {}
        self._block_of_slot: Dict[int, int] = {}
        self._free_slots = deque(range(n_slots))
        self.lanes = itertools.cycle(range(scheduler_lanes))

        # fault tolerance (DESIGN.md §11): optional admission/completion
        # journal + phase-boundary failure injector (serving/chaos.py),
        # the shared step watchdog, an injectable clock for deadline
        # tests, and the in-place recovery budget for run()
        self._journal = journal
        self._injector = injector
        self.watchdog = watchdog or StepWatchdog()
        self._clock = clock or time.time
        self.max_restarts = max_restarts
        self.lost_shards: set = set()

        self.active: Dict[int, Request] = {}     # slot -> request
        self.pending_tokens: Dict[int, List[int]] = {}
        self._latencies: List[float] = []
        self._ft_latencies: List[float] = []
        # wire the facade through the subsystems that emit (DESIGN §13)
        self.scheduler.telemetry = self.telemetry
        if self.prefix_cache is not None:
            self.prefix_cache.telemetry = self.telemetry
        self.flight.meta.update(
            dp=dp, b_local=b_local, page_size=int(cfg.page_size),
            pages_local=int(self.pages_local),
            lane_ell=classed_pool.lane_ell(self.state.pool, CLS_KV),
            size_classes=self.n_classes,
            expert_paging=self.expert_paging,
            expert_budget=self.expert_budget,
            speculate=self.speculate, arch=getattr(cfg, "name", "?"))

    @property
    def stats(self):
        """Backward-compatible live view of the typed telemetry
        counters (one ledger — external ``engine.stats[...]`` reads and
        writes land on the same dict :class:`Telemetry` maintains)."""
        return self.telemetry.counters

    # ---------------------------------------------------------- tracing
    def _tr_begin(self, name: str, tid: int, **args) -> None:
        """Idempotent span open: a resubmitted request (crash requeue,
        warm restart) must not double-open its span."""
        if not self.tracer.is_open(name, tid):
            self.tracer.begin(name, tid, **args)

    def _tr_end(self, name: str, tid: int, **args) -> None:
        if self.tracer.is_open(name, tid):
            self.tracer.end(name, tid, **args)

    def _trace_terminal(self, req, reason: str) -> None:
        """Close a request's spans on any terminal rejection path —
        also called by the scheduler's deadline/shed paths."""
        name = ("deadline_expired" if reason == "deadline"
                else "shed" if reason == "shed" else "reject")
        self.tracer.instant(name, tid=req.rid, reason=reason)
        self._tr_end("active", req.rid)
        self._tr_end("request", req.rid)

    # ------------------------------------------------------------ control
    @property
    def queue(self) -> List[Request]:
        """Backward-compatible view of the scheduler backlog (admission
        order: priority classes, FIFO within a class)."""
        return self.scheduler.pending()

    def _host_alloc_slot(self, shard: Optional[int] = None
                         ) -> Optional[int]:
        """O(1) wait-free admission through the paper's allocator.

        ``shard`` restricts placement: the scheduler's page-budget
        accounting is per shard, and a prefix-sharing donor's pages are
        only mappable from its own shard."""
        if not self._free_slots:
            return None
        if shard is not None:
            for s in self._free_slots:
                if s // self.bl == shard:
                    self._free_slots.remove(s)
                    self._free_slots.appendleft(s)
                    break
            else:
                return None
        lane = next(self.lanes)
        gen = self.slot_alloc.allocate(lane)
        try:
            while True:
                next(gen)
        except StopIteration as e:
            block = e.value
        op = self.lane_ctx.history[-1]
        self.telemetry.set_max("alloc_steps_max", op.steps)
        slot = self._free_slots.popleft()
        self._slot_of_block[block] = slot
        self._block_of_slot[slot] = block
        return slot

    def _host_free_slot(self, slot: int) -> None:
        lane = next(self.lanes)
        block = self._block_of_slot.pop(slot)
        self._slot_of_block.pop(block)
        gen = self.slot_alloc.free(lane, block)
        try:
            while True:
                next(gen)
        except StopIteration:
            pass
        self._free_slots.append(slot)

    # --------------------------------------------------- fault tolerance
    def _jrec(self, kind: str, **fields) -> None:
        if self._journal is not None:
            self._journal.record(kind, **fields)

    def _fire(self, phase: str, **ctx) -> None:
        if self._injector is not None:
            self._injector.fire(self, phase, **ctx)

    # ------------------------------------------------ scheduler interface
    def submit(self, req: Request) -> Admission:
        """Enqueue (or reject, with a reason) through the admission
        scheduler.  The return value is the backpressure signal."""
        now = self._clock()
        req.submitted_at = now
        if req.deadline_s > 0 and req.deadline_at == 0.0:
            req.deadline_at = now + req.deadline_s
        # write-ahead: the journal sees every request before the
        # scheduler does, carrying any resumed token prefix — recovery's
        # in_flight() replay is complete even if we crash mid-submit
        self._jrec("submit", rid=req.rid,
                   prompt=[int(t) for t in req.prompt],
                   max_new_tokens=int(req.max_new_tokens),
                   temperature=float(req.temperature),
                   top_k=int(req.top_k), seed=int(req.seed), slo=req.slo,
                   out_tokens=[int(t) for t in req.out_tokens],
                   preemptions=int(req.preemptions),
                   deadline_at=float(req.deadline_at))
        self._tr_begin("request", req.rid, slo=req.slo,
                       prompt_len=len(req.prompt))
        self.tracer.instant("submit", tid=req.rid, slo=req.slo)
        if self.expert_paging:
            # a footprint that cannot fit the expert budget even on an
            # empty shard is unservable — typed rejection, not a wedge
            need = (EXPERT_PPE * len(self._elayer_slots)
                    * len(self._footprint_of(req)))
            if need > self.expert_budget:
                self.scheduler._count("rejected")
                req.rejected = "too_large"
                self._jrec("reject", rid=req.rid, reason="too_large")
                self._trace_terminal(req, "too_large")
                return Admission(False, "too_large")
        adm = self.scheduler.submit(req, self.est_pages(req))
        if not adm.accepted:
            self._jrec("reject", rid=req.rid, reason=adm.reason)
            self._trace_terminal(req, adm.reason)
        return adm

    def est_pages(self, req: Request) -> int:
        """Worst-case page demand of a request: its full prompt plus
        its whole output budget, capped at the per-slot capacity.
        ``max_new_tokens`` is the TOTAL generation budget, so tokens
        already generated before a preemption are not added on top —
        the estimate is stable across preempt/readmit cycles (a grown
        estimate could outgrow a tight page budget and wedge the
        readmission)."""
        toks = len(req.prompt) + int(req.max_new_tokens)
        toks = min(max(toks, 1), self.capacity)
        return -(-toks // self.cfg.page_size)

    def est_state_blocks(self, req: Request) -> int:
        """Fine-class (CLS_STATE) block demand of a request: the fixed
        per-slot bounded-state footprint (rings/recurrent/encoder KV
        are sized by the model, not the request).  0 in a single-class
        config — the scheduler's state dimension then never binds."""
        return self._state_blocks

    def free_slot_shards(self) -> set:
        return {s // self.bl for s in self._free_slots}

    def prefix_match(self, req: Request, shard: Optional[int] = None):
        """Trie lookup, restricted to ``shard`` when given — the
        scheduler always restricts (donor pages are shard-local;
        DESIGN.md §9), the unrestricted form is diagnostic only."""
        if self.prefix_cache is None:
            return None
        toks = (list(req.prompt) + list(req.out_tokens)) or [1]
        return self.prefix_cache.match(toks, shard=shard)

    def pinned_pages_on(self, shard: int) -> int:
        return self.pins.pages_on(shard) if self.pins is not None else 0

    def pinned_pages(self) -> int:
        return self.pins.total_pages() if self.pins is not None else 0

    # ------------------------------------------- expert paging (§15)
    def _footprint_of(self, req: Request) -> tuple:
        """A request's admitted expert footprint (sorted, deduplicated;
        all experts when unrestricted)."""
        E = self.cfg.moe.num_experts
        if req.experts is None:
            return tuple(range(E))
        return tuple(sorted({int(e) for e in req.experts
                             if 0 <= int(e) < E}))

    def _fp_entry(self, fp: tuple):
        """(masks, row) for a footprint: the per-table bool[S, E] masks
        the bulk ref step consumes and the bool[E] register row.
        Cached — footprints repeat (that is the skew admission
        learns)."""
        ent = self._fp_masks.get(fp)
        if ent is None:
            E = self.cfg.moe.num_experts
            row = np.zeros(E, bool)
            row[list(fp)] = True
            masks = {
                pos: jnp.asarray(
                    np.broadcast_to(row, (tab.shape[0], E)).copy())
                for pos, tab in self.state.expert_tables.items()}
            ent = self._fp_masks[fp] = (masks, row)
        return ent

    def est_expert_pages(self, req: Request, shard: int) -> int:
        """Load-aware CLS_EXPERT page demand of a request ON A SHARD:
        0 for every (layer slot, expert) already resident there,
        EXPERT_PPE per cold one.  This is the per-shard skew signal the
        scheduler's third admission dimension consumes."""
        if self.expert_ledger is None:
            return 0
        led = self.expert_ledger
        fp = self._footprint_of(req)
        cold = sum(1 for pos, g in self._elayer_slots for e in fp
                   if not led.resident(shard, pos, g, e))
        return EXPERT_PPE * cold

    def expert_headroom(self, shard: int) -> int:
        """Admissible CLS_EXPERT pages on a shard: budget minus
        resident pages, plus what LRU eviction of COLD experts (zero
        active references) can reclaim.  Hot experts are working set,
        not cache — they never count as reclaimable."""
        if self.expert_ledger is None:
            return 1 << 30
        led = self.expert_ledger
        return (self.expert_budget - led.pages_on(shard)
                + led.evictable_pages(shard))

    def expert_pages_resident(self, shard: int) -> int:
        return (0 if self.expert_ledger is None
                else self.expert_ledger.pages_on(shard))

    def _load_expert(self, d: int, pos: str, g: int, e: int) -> None:
        w = self._host_experts[pos][g, e]
        counts = np.zeros((self.dp, self.bl), np.int32)
        counts[d, 0] = EXPERT_PPE
        oh = np.zeros(self.dp, bool)
        oh[d] = True
        self.state = self._eload[pos](
            self.state, jnp.asarray(counts), jnp.asarray(w),
            jnp.asarray(oh), jnp.int32(g), jnp.int32(e))
        self.expert_ledger.add(d, pos, g, e)
        self.telemetry.inc("expert_load_pages", EXPERT_PPE)

    def _evict_expert(self, key) -> None:
        d, pos, g, e = key
        self.expert_ledger.remove(key)
        oh = np.zeros(self.dp, bool)
        oh[d] = True
        self.state = self._eevict[pos](
            self.state, jnp.asarray(oh), jnp.int32(g), jnp.int32(e))
        self.telemetry.inc("expert_evictions")
        self.telemetry.inc("expert_evict_pages", EXPERT_PPE)

    def _admit_experts(self, slot: int, req: Request) -> None:
        """Bind the slot's expert footprint: set the router-mask
        register (both engines — token identity is by construction),
        and in paged mode make every footprint expert resident (LRU
        eviction for room; the scheduler's placement already verified
        headroom) and take one batch reference per expert."""
        if self.cfg.moe is None:
            return
        d, b = divmod(slot, self.bl)
        fp = self._footprint_of(req)
        masks, row = (self._fp_entry(fp) if self.expert_ledger is not None
                      else (None, None))
        if row is None:
            E = self.cfg.moe.num_experts
            row = np.zeros(E, bool)
            row[list(fp)] = True
        self.expert_mask = self.expert_mask.at[d, b].set(
            jnp.asarray(row))
        if self.expert_ledger is None:
            return
        led = self.expert_ledger
        keys = [(d, pos, g, e) for pos, g in self._elayer_slots
                for e in fp]
        for key in keys:
            if led.resident(*key):
                led.touch(key)
                self.telemetry.inc("expert_admit_hits")
                continue
            self.telemetry.inc("expert_admit_misses")
            while (led.pages_on(d) + EXPERT_PPE > self.expert_budget):
                victim = led.lru(d)
                assert victim is not None, (
                    "expert admission over budget with nothing "
                    "evictable — scheduler headroom check violated")
                self._evict_expert(victim)
            self._load_expert(*key)
        # ONE bulk addref over the whole footprint (admission-time
        # traffic, off the serve step)
        oh = np.zeros(self.dp, bool)
        oh[d] = True
        self.state = self._eref[False](self.state, masks,
                                       jnp.asarray(oh))
        for key in keys:
            led.addref(key)
        self._slot_experts[slot] = (d, fp)
        peak = max(led.pages_on(s) for s in range(self.dp))
        self.telemetry.set_max("expert_pages_resident_peak", peak)

    def _release_experts(self, slot: int, device: bool = True) -> None:
        """Drop the slot's expert references and reset its router-mask
        row to all-True (BOTH modes — the reset keeps resident and
        paged engines consistent, preserving token identity across a
        slot's whole lifecycle).  ``device=False`` on shard loss: the
        pages died with the shard, only host bookkeeping remains."""
        if self.cfg.moe is None:
            return
        d, b = divmod(slot, self.bl)
        self.expert_mask = self.expert_mask.at[d, b].set(True)
        ent = self._slot_experts.pop(slot, None)
        if ent is None or self.expert_ledger is None:
            return
        d, fp = ent
        if device:
            masks, _ = self._fp_entry(fp)
            oh = np.zeros(self.dp, bool)
            oh[d] = True
            self.state = self._eref[True](self.state, masks,
                                          jnp.asarray(oh))
        for pos, g in self._elayer_slots:
            for e in fp:
                self.expert_ledger.deref((d, pos, g, e))

    def flush_experts(self) -> int:
        """Evict every COLD resident expert (drained-engine teardown /
        leak audits — with active references nothing moves).  Returns
        the number of experts evicted."""
        if self.expert_ledger is None:
            return 0
        n = 0
        for key in [k for k, ent in self.expert_ledger.entries.items()
                    if ent["batch"] == 0]:
            self._evict_expert(key)
            n += 1
        return n

    def admit(self, req: Request, match, shard: int) -> int:
        """Place a request on ``shard`` (mechanism only — the scheduler
        chose the order, the shard, and verified budget/slot
        availability).  A preempted request re-enters here carrying its
        generated tokens: they are re-prefilled (often via the prefix
        cache) and generation resumes at ``out_count ==
        len(out_tokens)``, which both the budget check and the
        sampler's noise keying are relative to — so the resumed stream
        is the one the request would have produced unpreempted."""
        # empty prompts degrade to the BOS=1 convention
        toks = (list(req.prompt) + list(req.out_tokens)) or [1]
        slot = self._host_alloc_slot(shard)
        assert slot is not None, "scheduler admitted without a free slot"
        d, b = divmod(slot, self.bl)
        req.slot = slot
        self.active[slot] = req
        shared_n = 0
        if match is not None:
            # the scheduler guarantees shard-local matches; _try_share
            # asserts it, loudly — a cross-shard donor must never be
            # silently dropped (DESIGN.md §9)
            shared_n = self._try_share(slot, match, len(toks))
        self.pending_tokens[slot] = toks[shared_n:]
        self._fed[slot] = shared_n
        if self._alloc_state is not None and self._state_blocks > 0:
            # grant the slot's bounded-state blocks from the fine class
            # (CLS_STATE) in one bulk shared-pool pull — the class's
            # §4.2 slack plus the scheduler's state-budget accounting
            # guarantee the grant succeeds (DESIGN.md §14)
            counts = np.zeros((self.dp, self.bl), np.int32)
            counts[d, b] = self._state_blocks
            self.state = self._alloc_state(self.state, jnp.asarray(counts))
            self.telemetry.inc("state_blocks_granted", self._state_blocks)
        if self.prefix_cache is not None:
            self.prefix_cache.insert(slot, d, toks)
            self.prefix_cache.update_progress(slot, shared_n)
        if self.spec_store is not None:
            req._spec_key = self.spec_store.key_of(req.prompt)
        self.budget = self.budget.at[d, b].set(req.max_new_tokens)
        self.out_count = self.out_count.at[d, b].set(
            len(req.out_tokens))
        self.temps = self.temps.at[d, b].set(float(req.temperature))
        self.topks = self.topks.at[d, b].set(int(req.top_k))
        self.seeds = self.seeds.at[d, b].set(int(req.seed))
        self._admit_experts(slot, req)
        if req.temperature > 0:
            self._sampling_slots.add(slot)
        self.telemetry.inc("admitted")
        self._tr_begin("active", req.rid, slot=slot, shard=d)
        self.tracer.instant("admit", tid=req.rid, slot=slot, shard=d,
                            shared_tokens=shared_n)
        if req.out_tokens or req.preemptions:
            self.tracer.instant("resume", tid=req.rid,
                                tokens_done=len(req.out_tokens))
        self._jrec("admit", rid=req.rid, slot=slot, shard=d)
        return slot

    def preempt(self, slot: int) -> Request:
        """Evict a running request: pin its whole-page state when the
        budget allows (so readmission re-prefills through the cache),
        release its pages through the normal refcounted path, free the
        slot, and hand the request back to the scheduler carrying
        prompt + generated tokens."""
        req = self.active.pop(slot)
        toks = list(req.prompt) + list(req.out_tokens)
        self._maybe_pin(slot, toks)
        d, b = divmod(slot, self.bl)
        mask = np.zeros((self.dp, self.bl), bool)
        mask[d, b] = True
        self.state, _ = self._release(self.state, jnp.asarray(mask))
        self._release_experts(slot)
        self.pending_tokens.pop(slot, None)
        self._fed.pop(slot, None)
        self._pinned_slots.discard(slot)
        self._sampling_slots.discard(slot)
        if self.prefix_cache is not None:
            self.prefix_cache.remove(slot)
        self._host_free_slot(slot)
        self.scheduler.on_released(slot)
        req.slot = None
        req.preemptions += 1
        self.telemetry.inc("preemptions")
        self.tracer.instant("preempt", tid=req.rid, slot=slot)
        self._tr_end("active", req.rid)
        self._jrec("preempt", rid=req.rid)
        return req

    def fail_active(self, slot: int, reason: str, retry: bool = False
                    ) -> Request:
        """Terminate (or retry) a running request that hit a fault or an
        expired deadline: release its pages through the normal
        refcounted path, free the slot, then either park the request
        for a bounded-backoff retry or mark it terminally rejected with
        a typed reason (sched.FAILURE_REASONS)."""
        req = self.active.pop(slot)
        d, b = divmod(slot, self.bl)
        mask = np.zeros((self.dp, self.bl), bool)
        mask[d, b] = True
        self.state, _ = self._release(self.state, jnp.asarray(mask))
        self._release_experts(slot)
        self.pending_tokens.pop(slot, None)
        self._fed.pop(slot, None)
        self._pinned_slots.discard(slot)
        self._sampling_slots.discard(slot)
        if self.prefix_cache is not None:
            self.prefix_cache.remove(slot)
        self._host_free_slot(slot)
        self.scheduler.on_released(slot)
        req.slot = None
        self._tr_end("active", req.rid)
        if retry and req.retries < self.sched_config.retry_limit:
            req.retries += 1
            self.telemetry.inc("retries")
            self.tracer.instant("retry", tid=req.rid, reason=reason,
                                attempt=req.retries)
            self._jrec("preempt", rid=req.rid)
            self.scheduler.park(
                req, self.sched_config.retry_backoff * req.retries)
        else:
            req.rejected = reason
            self.telemetry.inc("failed")
            if reason == "deadline":
                self.telemetry.inc("deadline_expired")
            self._jrec("reject", rid=req.rid, reason=reason)
            self._trace_terminal(req, reason)
        return req

    def lose_shard(self, shard: int) -> None:
        """Graceful degradation on shard loss (DESIGN.md §11): the dead
        shard's device state — pages, pins, KV — is unreachable and
        leaves the accounting with the shard.  Its running requests are
        evacuated host-side through the requeue path (they re-prefill
        on a surviving shard, token-identically, since generation is a
        pure function of prompt + out_count); its slots are retired
        from service and admission shrinks to the survivors
        (runtime.elastic.plan_serving_for drives backlog shedding)."""
        if shard in self.lost_shards:
            return
        self.lost_shards.add(shard)
        self.telemetry.inc("shards_lost")
        self.tracer.instant("shard_loss", shard=shard)
        self._jrec("shard_lost", shard=shard)
        self.scheduler.lose_shard(shard)
        for slot in [s for s in self.active if s // self.bl == shard]:
            req = self.active.pop(slot)
            # host bookkeeping only: no device release — the shard that
            # owned the pages is gone
            self._release_experts(slot, device=False)
            self.pending_tokens.pop(slot, None)
            self._fed.pop(slot, None)
            self._pinned_slots.discard(slot)
            self._sampling_slots.discard(slot)
            if self.prefix_cache is not None:
                self.prefix_cache.remove(slot)
            self._host_free_slot(slot)
            self.scheduler.on_released(slot)
            req.slot = None
            req.preemptions += 1
            self.telemetry.inc("preemptions")
            self.tracer.instant("preempt", tid=req.rid, slot=slot,
                                reason="shard_loss")
            self._tr_end("active", req.rid)
            self._jrec("preempt", rid=req.rid)
            self.scheduler.requeue_front(req)
        if self.expert_ledger is not None:
            self.expert_ledger.drop_shard(shard)
        # retire the dead shard's slots from service entirely
        self._free_slots = deque(
            s for s in self._free_slots if s // self.bl != shard)
        if self.pins is not None:
            for pid in [p for p, e in self.pins.entries.items()
                        if e["shard"] == shard]:
                self.pins.remove(pid)
                if self.prefix_cache is not None:
                    self.prefix_cache.pin_remove(pid)
                self._jrec("unpin", pin_id=pid)
        if self.prefix_cache is not None:
            self.prefix_cache.roots.pop(shard, None)

    # ------------------------------------------------------------ pinning
    def _maybe_pin(self, slot: int, tokens: List[int]) -> None:
        """Pin the slot's resident whole pages for ``tokens`` (a prompt
        at prefill completion, prompt+generated at preemption) under
        the scheduler's pin policy.  Exact-key deduplicated: re-pinning
        a prefix that is already cache-held just refreshes its LRU."""
        if self.pins is None:
            return
        d, b = divmod(slot, self.bl)
        psz = self.cfg.page_size
        n_pages = min(len(tokens), self._fed.get(slot, 0)) // psz
        if n_pages < 1:
            return
        key_toks = tokens[:n_pages * psz]
        hit = self.pins.lookup(d, key_toks)
        if hit is not None:
            self.pins.touch(hit)
            return
        if not self.scheduler.may_pin(self, d, n_pages):
            return
        pin_id = self.pins.add(d, key_toks, n_pages)
        row = pin_id % self.pins.npin
        pin_oh = np.zeros((self.dp, self.pins.npin), bool)
        pin_oh[d, row] = True
        src = np.zeros((self.dp, self.bl), bool)
        src[d, b] = True
        pool, self.pin_tables = self._pin(
            self.state.pool, self.pin_tables, self.state.page_tables,
            jnp.asarray(pin_oh), jnp.asarray(src), jnp.int32(n_pages))
        self.state = self.state._replace(pool=pool)
        self.prefix_cache.pin_insert(pin_id, d, key_toks)
        self.telemetry.inc("pins_created")
        self.tracer.instant("pin", pin_id=pin_id, shard=d,
                            pages=int(n_pages))
        # write-behind: journaled only after the device op — a crash in
        # between leaves device refs the journal never saw, which
        # recovery reclaims (leak-fix, not leak)
        self._jrec("pin", pin_id=pin_id, shard=d, row=row,
                   tokens=[int(t) for t in key_toks], pages=int(n_pages))

    def evict_pin(self, pin_id: int) -> None:
        """Drop the cache's references on one pinned row (mechanism;
        the scheduler picks who and when)."""
        shard, row = self.pins.remove(pin_id)
        oh = np.zeros((self.dp, self.pins.npin), bool)
        oh[shard, row] = True
        pool, self.pin_tables = self._unpin(
            self.state.pool, self.pin_tables, jnp.asarray(oh))
        self.state = self.state._replace(pool=pool)
        self.prefix_cache.pin_remove(pin_id)
        self.tracer.instant("unpin", pin_id=pin_id, shard=shard)
        self._jrec("unpin", pin_id=pin_id)

    def flush_pins(self) -> int:
        """Evict every pinned prefix; returns how many.  After a full
        drain plus a flush, page occupancy must be exactly zero — the
        conservation check the overload bench and tests close with."""
        if self.pins is None:
            return 0
        ids = list(self.pins.entries)
        for pin_id in ids:
            self.evict_pin(pin_id)
        return len(ids)

    def _try_share(self, slot: int, match, prompt_len: int) -> int:
        """Map the matched prefix onto the donor's pages (device-side,
        one jitted call, off the per-token path).  The donor is either
        a live slot or a pinned cache row.  Returns the number of
        tokens now resident in the slot's KV (0 = no sharing)."""
        assert match.shard == slot // self.bl, (
            "cross-shard donor: page ids never alias across shards "
            "(DESIGN.md §9); the scheduler must match shard-locally")
        n = min(match.n_tokens, prompt_len - 1, self.capacity - 1)
        if n < self.cfg.page_size:
            return 0
        dst = np.zeros((self.dp, self.bl), bool)
        dst[slot // self.bl, slot % self.bl] = True
        if match.pinned:
            pin_id = pin_id_of(match.slot)
            pin_oh = np.zeros((self.dp, self.pins.npin), bool)
            pin_oh[match.shard, self.pins.entries[pin_id]["row"]] = True
            self.state, ok = self._share_pinned(
                self.state, self.pin_tables, jnp.asarray(dst),
                jnp.asarray(pin_oh), jnp.int32(n))
            if not bool(ok):   # shared pool dry for the COW page
                return 0
            self.pins.touch(pin_id)
            self.telemetry.inc("pin_hit_reqs")
            self.telemetry.inc("pin_hit_tokens", n)
            self.tracer.instant("pin_hit", tid=self.active[slot].rid,
                                tokens=n)
        else:
            src = np.zeros((self.dp, self.bl), bool)
            src[match.slot // self.bl, match.slot % self.bl] = True
            self.state, ok = self._share(self.state, jnp.asarray(dst),
                                         jnp.asarray(src), jnp.int32(n))
            if not bool(ok):   # lane dry for the COW page — admit unshared
                return 0
        self.telemetry.inc("prefix_shared_tokens", n)
        self.telemetry.inc("prefix_shared_reqs")
        rid = self.active[slot].rid
        self.tracer.instant("share", tid=rid, tokens=n,
                            shard=match.shard, pinned=bool(match.pinned))
        if n % self.cfg.page_size != 0:
            # the share step gave the slot a private copy of the donor's
            # partial tail page — the COW copy counter lives here, at
            # the share-step boundary, because COW never happens inside
            # _serve_step (DESIGN.md §13: counted host-side, zero extra
            # transfer — ``ok`` already crossed in the share's own sync)
            self.telemetry.inc("cow_copies")
            self.tracer.instant("cow_copy", tid=rid)
        return n

    # -------------------------------------------------------------- step
    def _gate_k(self, key, k_max: int) -> int:
        """Break-even draft length for this prefix (DESIGN.md §12).

        Expected tokens per step at accept rate ``a`` with k drafts is
        ``1 + a + a^2 + ... + a^k`` (draft i lands only if every earlier
        draft did); a width-(k+1) verify step costs ``cost(k+1, spec) /
        cost(1, decode)`` plain steps, from the measured per-step EWMA
        when both widths have run, else the linear fallback model.
        Returns the largest k <= k_max whose expected tokens clear its
        cost — so draft_len SHRINKS before speculation disables — or 0
        to skip drafting this prefix.  An unmeasured prefix drafts at
        k_max: optimism is how the EWMA gets its first sample.
        """
        if not self.spec_gate or k_max <= 0:
            return k_max
        a = self.spec_store.accept_rate(key)
        if a is None:
            return k_max
        c1 = self._step_cost.get((1, False))
        exp_tokens = 1.0
        gain = 1.0
        best = 0
        for k in range(1, k_max + 1):
            gain *= a
            exp_tokens += gain
            ck = self._step_cost.get((k + 1, True))
            ratio = (ck / c1 if c1 and ck
                     else 1.0 + self.spec_cost_slope * k)
            if exp_tokens >= ratio:
                best = k
        return best

    def _build_drafts(self, limit: int) -> Dict[int, List[int]]:
        """Host-side draft proposals for this step's generating slots,
        from the hot-prefix continuation store.  Drafted ONCE per hot
        prefix per step: slots at the same (prefix, context) reuse one
        lookup.  Never reads device state — the step keeps its single
        sync.  Caps keep drafts within the slot's page-table capacity
        and output budget (a draft past either is guaranteed waste);
        the accept-rate gate then shrinks or zeroes the draft length
        for prefixes whose measured accept rate can't pay for the wider
        verify lane."""
        out: Dict[int, List[int]] = {}
        if limit <= 0:
            return out
        memo: Dict[tuple, List[int]] = {}
        for slot, req in self.active.items():
            if self.pending_tokens[slot] or req._spec_key is None:
                continue
            key = req._spec_key
            k = min(limit, self.draft_len,
                    self.capacity - 1 - self._fed[slot],
                    req.max_new_tokens - len(req.out_tokens) - 1)
            if k <= 0:
                continue
            k_gated = self._gate_k(key, k)
            if k_gated <= 0:
                self.telemetry.inc("spec_gate_skips")
                continue
            suffix = tuple(req.prompt[len(key):]) + tuple(req.out_tokens)
            mk = (key, suffix, k_gated)
            if mk not in memo:
                memo[mk] = self.spec_store.draft(key, suffix, k_gated)
            if memo[mk]:
                out[slot] = memo[mk]
        return out

    def step(self) -> bool:
        """One engine step.  Returns True iff device work was
        dispatched (False = idle fast-path: admission ran but nothing
        is active, so the jitted step — and its sync — are skipped).

        The named ``_fire`` points are the chaos-injection phase
        boundaries (serving/chaos.py PHASES): ``feed`` fires BEFORE any
        per-slot feed mutation so a fault there leaves host and device
        consistent; ``post_sync`` fires after the device round-trip but
        before bookkeeping/journaling — a crash there loses this step's
        tokens and recovery must regenerate them.

        Each part runs inside a host phase span (``Tracer.phase``,
        DESIGN.md §13): ``serve.step`` around ``serve.admit``,
        ``serve.feed``, ``serve.dispatch``, ``serve.sync``,
        ``serve.post`` and ``serve.flight``."""
        with self.tracer.phase("serve.step"):
            return self._step()

    def _step(self) -> bool:
        phase = self.tracer.phase
        t0 = time.perf_counter()
        with phase("serve.admit"):
            self._fire("pre_tick")
            self.scheduler.tick(self)
            self._fire("post_admission")
        if not self.active:
            self.telemetry.inc("idle_steps")
            return False
        with phase("serve.feed"):
            T, drafts, feed, gen_slots = self._build_feed()
        with phase("serve.dispatch"):
            spec = any(gen_slots.values())
            status = self._dispatch(T, spec, feed)
        with phase("serve.sync"):
            status = np.asarray(status)  # the step's ONE device->host sync
        with phase("serve.post"):
            now, ctr_block = self._post_sync(T, status, gen_slots)
        dt = time.perf_counter() - t0
        with phase("serve.flight"):
            self._record_step(T, spec, dt, now, status, ctr_block, drafts)
        return True

    def _build_feed(self):
        """This step's lane width, drafts, feed arrays and generating
        slots (slot -> drafts fed)."""
        # schedule this step's lane widths (host-side bookkeeping only —
        # no device sync; prompt chunks come from host queues, decode
        # tokens from the device-resident last_tok register, draft
        # tokens from the continuation store).  The prefill width is the
        # scheduler's SLO-aware bucket choice; an all-decode step runs
        # width 1, widened to draft_len + 1 when drafts exist.
        any_prompt = any(self.pending_tokens[s] for s in self.active)
        T = self.scheduler.pick_chunk(self, self.chunk) if any_prompt else 1
        drafts: Dict[int, List[int]] = {}
        if self.spec_store is not None:
            if not any_prompt:
                drafts = self._build_drafts(self._spec_T - 1)
                if drafts:
                    T = self._spec_T
            elif T > 1:
                # drafts ride mixed prompt/decode steps too: projection
                # slimming made the spec variant's extra cost k + 1
                # gathered vocab rows per slot instead of a T-wide
                # projection, so a decode slot sharing a step with
                # prefill chunks no longer starves of speculation
                # (DESIGN.md §12; PR 5 restricted drafts to all-decode
                # steps precisely because of that T-wide cost)
                drafts = self._build_drafts(min(self._spec_T, T) - 1)
                if drafts:
                    self.telemetry.inc("spec_mixed_steps")
        self._fire("feed", rids={req.rid: slot
                                 for slot, req in self.active.items()})
        prompt_toks = np.zeros((self.dp, self.bl, T), np.int32)
        feed_lens = np.zeros((self.dp, self.bl), np.int32)
        is_prompt = np.zeros((self.dp, self.bl), bool)
        emit = np.zeros((self.dp, self.bl), bool)
        gen_slots: Dict[int, int] = {}       # slot -> drafts fed
        for slot, req in self.active.items():
            d, b = divmod(slot, self.bl)
            pend = self.pending_tokens[slot]
            if pend:
                # never feed past the page-table capacity — a slot that
                # reaches it finishes via the on-device length check
                n = min(len(pend), T, self.capacity - self._fed[slot])
                prompt_toks[d, b, :n] = pend[:n]
                del pend[:n]
                feed_lens[d, b] = n
                is_prompt[d, b] = True
                emit[d, b] = not pend
                self.telemetry.inc("prompt_tokens", n)
                self.tracer.instant("prefill_chunk", tid=req.rid,
                                    tokens=n, fed=self._fed[slot] + n)
                if (emit[d, b] and self.pins is not None
                        and slot not in self._pinned_slots
                        and self._fed[slot]
                        >= len(req.prompt) // self.cfg.page_size
                        * self.cfg.page_size):
                    # the prompt completes THIS step and its whole pages
                    # are ALREADY resident — `_fed` is read before this
                    # chunk is added, so the gate only passes when the
                    # chunk covers nothing but the partial tail: pin
                    # now, before dispatch — a request that finishes on
                    # this very step (max_new=1, instant EOS) releases
                    # in-device and could never pin after.  A prompt
                    # whose final whole page rides in THIS chunk pins on
                    # the post-status path below instead.
                    self._pinned_slots.add(slot)
                    self._maybe_pin(slot, list(req.prompt))
                self._fed[slot] += n
            else:
                dr = drafts.get(slot, [])
                if dr:
                    prompt_toks[d, b, 1:1 + len(dr)] = dr
                feed_lens[d, b] = 1 + len(dr)
                emit[d, b] = True
                # the KV the lane keeps (== tokens emitted) is only
                # known after verification: _fed advances on status read
                gen_slots[slot] = len(dr)
        return T, drafts, (prompt_toks, feed_lens, is_prompt, emit), gen_slots

    def _dispatch(self, T: int, spec: bool, feed) -> jax.Array:
        """Dispatch the jitted serve step on ``feed``; returns its
        packed status, still on the device."""
        sampler = bool(self._sampling_slots)
        self.step_variants.add((T, spec, sampler))
        serve = self._serve_variants[(sampler, spec)]
        self.state, self.last_tok, self.out_count, status = serve(
            self.params, self.state, self.last_tok, self.out_count,
            self.budget, self.temps, self.topks, self.seeds,
            *(jnp.asarray(a) for a in feed), self.expert_mask)
        self.telemetry.inc("steps")
        self.telemetry.observe_hist("chunk_hist", T)
        self._fire("dispatched")
        return status

    def _post_sync(self, T: int, status: np.ndarray,
                   gen_slots: Dict[int, int]) -> Tuple[float, np.ndarray]:
        """Per-slot bookkeeping on the synced status: tokens, finishes,
        prefix progress, pins.  Returns the engine clock's reading for
        them and the device counter block."""
        self._fire("post_sync")
        n_emit = status[T + STATUS_EMITTED]
        done_row = status[T + STATUS_DONE]
        pages_row = status[T + STATUS_PAGES]
        # device counter block: the N_CTR trailing rows (per-shard
        # values broadcast over Bl — column 0 is the value)
        ctr_block = status[T + 3:, :, 0]
        self.telemetry.absorb_counter_block(ctr_block)

        self.pages_used_shard = [int(x) for x in pages_row[:, 0]]
        pages_now = int(pages_row[:, 0].sum())
        self.telemetry.set_max("pages_peak", pages_now)
        self.telemetry.inc("pages_sum", pages_now)
        row = pages_row[:, 0].astype(np.int64)
        self._pages_shard_sum += row
        np.maximum(self._pages_shard_peak, row, out=self._pages_shard_peak)

        now = self._clock()
        psz = self.cfg.page_size
        for slot, req in list(self.active.items()):
            d, b = divmod(slot, self.bl)
            ne = int(n_emit[d, b])
            if ne:
                toks = [int(status[j, d, b]) for j in range(ne)]
                req.out_tokens.extend(toks)
                self._jrec("tokens", rid=req.rid, toks=toks)
                self.telemetry.inc("tokens_out", ne)
                if req.first_token_at == 0.0:
                    req.first_token_at = now
                    self._ft_latencies.append(now - req.submitted_at)
                    self.tracer.instant("first_token", tid=req.rid)
            if slot in gen_slots:
                k = gen_slots[slot]
                if k:
                    acc = max(ne - 1, 0)
                    self.telemetry.inc("spec_lanes")
                    self.telemetry.inc("spec_drafted", k)
                    self.telemetry.inc("spec_accepted", acc)
                    self.telemetry.observe_hist("accept_hist", acc)
                    self.tracer.instant("spec_accept", tid=req.rid,
                                        drafted=k, accepted=acc)
                    if req._spec_key is not None:
                        # feed the per-prefix accept-rate EWMA the gate
                        # reads (n_emit may be budget/EOS-truncated
                        # below the true accept count — a conservative
                        # under-estimate on the request's last step)
                        self.spec_store.observe(req._spec_key, k, acc)
                    # whole-page rollback accounting (host math on the
                    # _fed shadow — no extra sync): the lane fed 1 + k
                    # tokens but kept only ne
                    fed0 = self._fed[slot]
                    over = (-(-(fed0 + 1 + k) // psz)
                            - (-(-(fed0 + ne) // psz)))
                    self.telemetry.inc("spec_pages_rolled_back", over)
                    if over:
                        self.tracer.instant("spec_rollback",
                                            tid=req.rid, pages=over)
                self._fed[slot] += ne
            if done_row[d, b]:
                # pages were already released inside the jitted step
                req.done = True
                req.finished_at = now
                self._latencies.append(now - req.submitted_at)
                self.active.pop(slot)
                self._release_experts(slot)
                self.pending_tokens.pop(slot, None)
                self._pinned_slots.discard(slot)
                self._sampling_slots.discard(slot)
                if self.prefix_cache is not None:
                    self.prefix_cache.remove(slot)
                if self.spec_store is not None and req._spec_key:
                    # feed the continuation history: this finished
                    # stream is the next draft for its hot prefix
                    self.spec_store.record(
                        req._spec_key,
                        tuple(req.prompt[len(req._spec_key):])
                        + tuple(req.out_tokens))
                self._host_free_slot(slot)
                self.scheduler.on_released(slot)
                self.tracer.instant("finish", tid=req.rid,
                                    tokens=len(req.out_tokens))
                self._tr_end("active", req.rid)
                self._tr_end("request", req.rid)
                self._jrec("finish", rid=req.rid)
            else:
                if self.prefix_cache is not None:
                    # this step's feed is now in device KV: the slot can
                    # donate that much of its prompt to future admissions
                    self.prefix_cache.update_progress(slot, self._fed[slot])
                if (self.pins is not None
                        and slot not in self._pinned_slots
                        and not self.pending_tokens[slot]):
                    # prompt fully resident (this step's pages included —
                    # the pin runs AFTER the step that allocated them):
                    # retain its whole pages past the request's lifetime
                    self._pinned_slots.add(slot)
                    self._maybe_pin(slot, list(req.prompt))
        self._fire("post_step")
        return now, ctr_block

    def _record_step(self, T: int, spec: bool, dt: float, now: float,
                     status: np.ndarray, ctr_block: np.ndarray,
                     drafts: Dict[int, List[int]]) -> None:
        """Feed the step's wall time ``dt`` (entry to the end of
        ``serve.post``) to the cost model, the watchdog and the flight
        recorder."""
        # measured per-step cost model for the break-even gate: EWMA of
        # wall time keyed (lane width, spec).  The first dispatch at a
        # key pays jit compilation, so it is discarded — the second
        # sample seeds the EWMA.
        ck = (T, spec)
        if ck in self._cost_seen:
            prev = self._step_cost.get(ck)
            self._step_cost[ck] = dt if prev is None else (
                0.8 * prev + 0.2 * dt)
        else:
            self._cost_seen.add(ck)
        verdict = self.watchdog.observe(self.stats["steps"], dt)
        if verdict == "straggler":
            self.telemetry.inc("stragglers")
        elif verdict == "timeout":
            self.telemetry.inc("step_timeouts")
        if verdict is not None:
            self.tracer.instant("watchdog", verdict=verdict,
                                step=self.stats["steps"])
        # flight recorder (DESIGN.md §13): ring-buffer this step's full
        # forensic record — the packed status (tokens + bookkeeping +
        # counter block), the gate decisions that shaped the dispatch,
        # and the watchdog verdict
        self.flight.record(
            step=self.stats["steps"], t=now, T=T, spec=spec,
            status=status.tolist(),
            ctr=ctr_block.tolist(),
            drafts={int(s): len(d) for s, d in drafts.items()},
            rids={int(s): int(r.rid) for s, r in self.active.items()},
            watchdog=verdict, dt_ms=round(dt * 1e3, 3))
        if verdict == "timeout" and self.flight.dump(
                "watchdog_timeout", {"step": self.stats["steps"]}):
            self.telemetry.inc("flight_dumps")
            self.tracer.instant("flight_dump", reason="watchdog_timeout")

    def compiled_step_text(self, T: int, spec: bool = False,
                           sampler: bool = False) -> str:
        """Compiled HLO text of the ``(sampler, spec)`` serve-step
        variant at lane width ``T`` for this engine's state — where a
        check finds the step's Pallas kernels (``tpu_custom_call``) and
        counts its collectives (:func:`step_collectives`).  Lowers and
        compiles without running; the compile caches make a variant the
        engine already ran cheap."""
        feed = (jax.ShapeDtypeStruct((self.dp, self.bl, T), jnp.int32),
                jax.ShapeDtypeStruct((self.dp, self.bl), jnp.int32),
                jax.ShapeDtypeStruct((self.dp, self.bl), bool),
                jax.ShapeDtypeStruct((self.dp, self.bl), bool))
        return self._serve_variants[(sampler, spec)].lower(
            self.params, self.state, self.last_tok, self.out_count,
            self.budget, self.temps, self.topks, self.seeds, *feed,
            self.expert_mask).compile().as_text()

    def idle(self) -> bool:
        """Nothing running and nothing admissible: the batch is empty
        and so is the scheduler backlog (rejected requests are terminal
        — they never hold ``run`` open; parked retries do)."""
        return not self.active and self.scheduler.backlog() == 0

    def run(self, max_steps: int = 10_000,
            max_restarts: Optional[int] = None) -> None:
        """Exception-safe driver (DESIGN.md §11).

        * :class:`~repro.serving.chaos.PoisonedRequest` fails exactly
          the offending request (bounded retry, then terminal
          ``rejected="poisoned"``) — everyone else keeps running;
        * :class:`~repro.serving.chaos.HostCrash` re-raises — host
          state is gone by definition and only
          :func:`~repro.serving.chaos.recover_engine` may rebuild it;
        * any other exception triggers an in-place recovery (requeue
          all active work, reconcile the pool) and, past the restart
          budget, re-raises AFTER recovering — so pool conservation
          holds even on the propagating path.
        """
        budget = self.max_restarts if max_restarts is None else max_restarts
        restarts = 0
        for _ in range(max_steps):
            if self.idle():
                break
            try:
                self.step()
            except PoisonedRequest as e:
                if e.slot in self.active:
                    self.fail_active(e.slot, "poisoned", retry=True)
            except HostCrash:
                # host state dies here by definition: the flight ring is
                # the crash's forensic record — dump it on the way out
                # (recover_engine re-dumps with the reconcile report)
                self.tracer.instant("crash", step=self.stats["steps"])
                if self.flight.dump("host_crash",
                                    {"step": self.stats["steps"]}):
                    self.telemetry.inc("flight_dumps")
                raise
            except Exception as e:
                restarts += 1
                if self.flight.dump("step_error",
                                    {"step": self.stats["steps"],
                                     "error": repr(e)}):
                    self.telemetry.inc("flight_dumps")
                    self.tracer.instant("flight_dump",
                                        reason="step_error")
                self._recover_inplace()
                if restarts > budget:
                    raise

    # ----------------------------------------------------- crash recovery
    def adopt_crashed_state(self, dead_state: DecodeState,
                            pin_np: Optional[np.ndarray]) -> dict:
        """Install a crashed engine's surviving device state (also the
        tail of :meth:`_recover_inplace`): keep the KV page content —
        pinned pages' data lives there — reconcile the pool against the
        trusted pin rows via :func:`hier_pool.audit_and_reconcile`, and
        clear every per-slot mapping and register (all in-flight work
        re-enters through the preemption-resume path).  Returns the
        reconcile report."""
        assert not self.active, "adopt with active slots"
        dp, bl, maxp = self.state.page_tables.shape
        C = len(dead_state.pool.classes)
        # pins live only in the KV class; every other class reconciles
        # against no keep rows (all grants belonged to requeued slots)
        pins = None if pin_np is None else tuple(
            [pin_np] + [None] * (C - 1))
        pool, report = classed_pool.audit_and_reconcile(
            dead_state.pool, keep_tables=None, pin_tables=pins)

        def zero(t):
            return jax.tree.map(jnp.zeros_like, t)

        state = dead_state._replace(
            pool=pool,
            page_tables=jnp.full((dp, bl, maxp), NULL, jnp.int32),
            seq_lens=jnp.zeros((dp, bl), jnp.int32),
            rings=zero(dead_state.rings), rec=zero(dead_state.rec))
        if dead_state.state_tables is not None:
            state = state._replace(state_tables=jnp.full_like(
                dead_state.state_tables, NULL))
        if dead_state.expert_tables is not None:
            # the reconcile passed no keep/pin rows for CLS_EXPERT, so
            # every expert page was reclaimed — NULL the tables, clear
            # the host ledger, and let the next admissions reload
            # (read-only weights re-materialize from the host store)
            state = state._replace(expert_tables={
                pos: jnp.full_like(tab, NULL)
                for pos, tab in dead_state.expert_tables.items()})
            if self.expert_ledger is not None:
                self.expert_ledger.clear()
            self._slot_experts.clear()
        if self.mesh is not None:
            state = jax.device_put(
                state, serve_shardings(self.mesh, self._pspecs))
        self.state = state
        self.last_tok, self.out_count, self.budget = \
            empty_serve_arrays(self.dp, self.bl)
        self.temps = jnp.zeros((self.dp, self.bl), jnp.float32)
        self.topks = jnp.zeros((self.dp, self.bl), jnp.int32)
        self.seeds = jnp.zeros((self.dp, self.bl), jnp.int32)
        self.expert_mask = jnp.ones_like(self.expert_mask)
        if self.pin_tables is not None:
            self.pin_tables = (jnp.asarray(pin_np) if pin_np is not None
                               else jnp.full_like(self.pin_tables, NULL))
        if self.mesh is not None:
            reg_ns = NamedSharding(self.mesh, self._rspec)
            (self.last_tok, self.out_count, self.budget, self.temps,
             self.topks, self.seeds, self.expert_mask) = jax.device_put(
                (self.last_tok, self.out_count, self.budget, self.temps,
                 self.topks, self.seeds, self.expert_mask), reg_ns)
            if self.pin_tables is not None:
                self.pin_tables = jax.device_put(self.pin_tables, reg_ns)
        self.pending_tokens.clear()
        self._fed.clear()
        self._pinned_slots.clear()
        self._sampling_slots.clear()
        # structured reconcile report through the tracer (DESIGN §13) —
        # recovery is never silent reconstruction
        self.tracer.instant(
            "reconcile",
            reclaimed=int(report.get("reclaimed", 0)),
            resurrected=int(report.get("resurrected", 0)),
            clamped=int(report.get("clamped", 0)),
            never_dry=bool(report.get("never_dry", True)),
            conserved=bool(report.get("conserved", True)))
        if self.flight.dump("audit_and_reconcile", {"report": report}):
            self.telemetry.inc("flight_dumps")
        return report

    def _recover_inplace(self) -> dict:
        """Restore a consistent engine after a failed step without
        losing the process: requeue every active request through the
        preemption path (host bookkeeping only — the device may be
        mid-operation, so per-slot release cannot be trusted) and
        rebuild the pool from the ledger-trusted pin rows.  The host
        survived, so the pin LEDGER is current; a device pin op whose
        ledger insert never ran is reclaimed, exactly as in the
        post-crash path."""
        self.telemetry.inc("recoveries")
        self.tracer.begin("recover", kind="inplace")
        for slot in list(self.active):
            req = self.active.pop(slot)
            # host bookkeeping only — the device is mid-operation, so
            # per-slot expert deref cannot be trusted; the reconcile
            # below reclaims every expert page regardless
            self._release_experts(slot, device=False)
            self.pending_tokens.pop(slot, None)
            self._fed.pop(slot, None)
            self._pinned_slots.discard(slot)
            self._sampling_slots.discard(slot)
            if self.prefix_cache is not None:
                self.prefix_cache.remove(slot)
            self._host_free_slot(slot)
            self.scheduler.on_released(slot)
            req.slot = None
            req.preemptions += 1
            self.telemetry.inc("preemptions")
            self.tracer.instant("preempt", tid=req.rid, slot=slot,
                                reason="recovery")
            self._tr_end("active", req.rid)
            self._jrec("preempt", rid=req.rid)
            self.scheduler.requeue_front(req)
        pin_np = None
        if self.pin_tables is not None:
            pin_np = np.asarray(self.pin_tables).copy()
            ok = np.zeros(pin_np.shape[:2], bool)
            for e in self.pins.entries.values():
                ok[e["shard"], e["row"]] = True
            pin_np[~ok] = NULL
        report = self.adopt_crashed_state(self.state, pin_np)
        self.tracer.end("recover")
        return report

    def leak_free(self) -> bool:
        """Zero live pages on every surviving shard (a dead shard's
        pages are unreachable by definition — they leave the accounting
        with the shard).  The post-drain + flush_pins invariant every
        chaos run closes with."""
        live = sum(np.asarray(classed_pool.live_per_shard(self.state.pool, c))
                   for c in range(self.n_classes))
        return all(int(live[s]) == 0 for s in range(self.dp)
                   if s not in self.lost_shards)

    # ------------------------------------------------------- warm restart
    def save_warm(self, ckptr, step: int = 0) -> None:
        """Persist the serving plane's warm state through the sharded
        checkpointer: the DecodeState (pool + pinned KV content), the
        device pin table, and a JSON sidecar with the host ledgers —
        pin entries, speculation streams, and still-queued requests.
        Must be called drained (no active slots): queued work requeues
        exactly, but a running slot's device KV is not snapshot-
        consistent with a host mid-step."""
        assert not self.active, "drain the engine before a warm save"
        ckptr.wait()
        payload = {"state": self.state}
        if self.pin_tables is not None:
            payload["pin_tables"] = self.pin_tables
        aux = {
            "pins": (self.pins.to_state() if self.pins is not None else []),
            "spec": (self.spec_store.to_state()
                     if self.spec_store is not None else None),
            "queued": [{
                "rid": int(r.rid),
                "prompt": [int(t) for t in r.prompt],
                "max_new_tokens": int(r.max_new_tokens),
                "temperature": float(r.temperature),
                "top_k": int(r.top_k), "seed": int(r.seed), "slo": r.slo,
                "out_tokens": [int(t) for t in r.out_tokens],
                "preemptions": int(r.preemptions),
                "deadline_at": float(r.deadline_at),
            } for r in self.scheduler.pending()],
        }
        ckptr.save(step, payload, aux=aux)

    def restore_warm(self, ckptr, step: Optional[int] = None) -> int:
        """Rebuild a freshly constructed engine from a warm save: adopt
        the device arrays (pool, pinned KV pages, pin table), reload
        the pin ledger + prefix-trie pin entries and the speculation
        store, and resubmit the queued requests.  The first post-
        restart hot-prefix request shares pinned pages and drafts
        without any re-prefill — the ROADMAP's warm-restart contract."""
        if step is None:
            step = ckptr.latest_step()
        assert step is not None, "no complete warm checkpoint to restore"
        like = {"state": self.state}
        if self.pin_tables is not None:
            like["pin_tables"] = self.pin_tables
        got = ckptr.restore(step, like)
        state = got["state"]
        if self.mesh is not None:
            state = jax.device_put(
                state, serve_shardings(self.mesh, self._pspecs))
        self.state = state
        if self.pin_tables is not None and "pin_tables" in got:
            self.pin_tables = got["pin_tables"]
            if self.mesh is not None:
                self.pin_tables = jax.device_put(
                    self.pin_tables, NamedSharding(self.mesh, self._rspec))
        aux = ckptr.restore_aux(step) or {}
        if self.pins is not None and aux.get("pins"):
            self.pins.load_state(aux["pins"])
            if self.prefix_cache is not None:
                for pid, e in self.pins.entries.items():
                    self.prefix_cache.pin_insert(pid, e["shard"],
                                                 list(e["tokens"]))
        if self.spec_store is not None and aux.get("spec"):
            self.spec_store.load_state(aux["spec"])
        for spec in aux.get("queued", []):
            req = Request(rid=int(spec["rid"]),
                          prompt=list(spec["prompt"]),
                          max_new_tokens=int(spec["max_new_tokens"]),
                          temperature=float(spec["temperature"]),
                          top_k=int(spec["top_k"]),
                          seed=int(spec["seed"]), slo=spec["slo"],
                          out_tokens=list(spec["out_tokens"]))
            req.preemptions = int(spec.get("preemptions", 0))
            req.deadline_at = float(spec.get("deadline_at", 0.0))
            self.submit(req)
        return step

    # ------------------------------------------------------------ metrics
    def blocks_in_use(self, cls: int = CLS_KV) -> int:
        """Blocks of one size class currently referenced across shards
        (shared pages count once)."""
        total = classed_pool.pages_local(self.state.pool, cls) * self.dp
        return total - int(hier_pool.total_free(
            classed_pool.cls_pool(self.state.pool, cls)))

    def pages_in_use(self) -> int:
        """Physical KV pages currently referenced (shared pages count
        once; includes cache-pinned pages — see :meth:`pinned_pages`).
        Coarse-class quantity; see :meth:`blocks_in_use` for the fine
        classes."""
        return self.blocks_in_use(CLS_KV)

    def page_occupancy(self) -> float:
        return self.pages_in_use() / (self.pages_local * self.dp)

    def pages_mean(self) -> float:
        """Mean pages-in-use per step (from the packed status row)."""
        return self.stats["pages_sum"] / max(self.stats["steps"], 1)

    def shard_occupancy(self) -> Dict[str, list]:
        """Per-shard pages-in-use statistics over the run (from the
        status row's PAGES entries — no extra sync): the mesh bench's
        load-balance axes, and the admission scheduler's placement
        quality in one place."""
        steps = max(self.stats["steps"], 1)
        return {
            "pages_mean_shard": [round(float(x) / steps, 1)
                                 for x in self._pages_shard_sum],
            "pages_peak_shard": [int(x) for x in self._pages_shard_peak],
            "mesh_devices": 0 if self.mesh is None else self.mesh.size,
        }

    def latency_quantiles(self) -> Dict[str, float]:
        """p50/p99 end-to-end and first-token latency (seconds) over
        finished requests — the overload bench's measured axes."""
        def q(xs, f):
            if not xs:
                return 0.0
            s = sorted(xs)
            return s[min(len(s) - 1, int(round(f * (len(s) - 1))))]
        return {"p50_s": q(self._latencies, 0.50),
                "p99_s": q(self._latencies, 0.99),
                "first_token_p50_s": q(self._ft_latencies, 0.50),
                "first_token_p99_s": q(self._ft_latencies, 0.99)}
