"""Prefix sharing: host-side radix trie + device-side share/COW step.

Identical prompt prefixes from concurrent requests map onto the same
physical KV pages (the dominant memory win under production request
rates — a hot system prompt is stored once, not once per slot).

Split of responsibilities (DESIGN.md §7):

* :class:`PrefixCache` — a **host-side** radix trie over the prompts of
  *live* slots, at page granularity (one trie level per ``page_size``
  tokens).  It matches an incoming prompt against live prefixes and
  answers with a donor slot and a token count — never a page id: page
  ids stay device-resident (the host performs one sync per serving
  step and never reads tables back).
* :func:`share_prefix_step` — a **jitted device step**, called once per
  admission-with-match (off the per-token hot path).  It copies the
  donor's full-page table entries into the new slot's table and
  registers the extra references (``hier_pool.addref`` on the pool's
  int16 refcounts), and performs the copy-on-write for the one partial
  page the new slot will append into: a fresh page from the slot's
  private lane, the donor's page content copied across all paged
  layers.  The per-token step then needs no sharing logic at all —
  appends only ever write at positions >= seq_lens (never into a
  shared page), and release decrements refcounts instead of freeing
  (:func:`hier_pool.free_n`).

Matches are shard-local by construction (page ids are private to a DP
shard), so the trie is kept per shard and the engine prefers placing a
request on its donor's shard.

Only models whose whole decode state is paged can share (ring /
recurrent layers would need their donor's state *at the match point*,
which no longer exists); the engine auto-disables sharing otherwise.

**Pinned prefixes (DESIGN.md §8).**  Without pinning, the trie only
tracks *live* slots, so a hot system prompt is re-prefilled from
scratch the moment its last request finishes.  Pinning keeps a
finished prefix's pages alive by giving the cache its *own* reference
on each page: :func:`pin_prefix_step` copies the first ``n_pages`` of
a live slot's table into a device-resident **pin table** row and
``addref``\\ s them, so when the slot later releases inside the jitted
step the pages drop to refcount 1 (cache-owned) instead of 0 — the
conservation invariants from the refcount protocol carry over
unchanged (a pinned page is simply a page with one more owner).
Pinned rows are donors like any live slot: the trie stores them under
negative pseudo-slot ids and :func:`share_pinned_step` maps them into
a new slot exactly as :func:`share_prefix_step` maps a live donor —
including the COW copy of a mid-page tail, whose source content is
still resident because a refcount ≥ 1 page is never restacked.
:func:`unpin_step` releases the cache's references (eviction);
:class:`PinnedPrefixes` is the host ledger (LRU order, per-shard pages
budget, row assignment) the scheduler drives the policy through.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core import classed_pool
from ..core.block_pool import NULL
from ..core.classed_pool import CLS_KV


# ------------------------------------------------------------- host trie

#: Pinned entries live in the same trie as live slots, keyed by
#: negative pseudo-slot ids: pin_id 0, 1, 2, ... <-> -2, -3, -4, ...
#: (-1 is reserved — it reads as NULL in device land).
PIN_BASE = -2


def pin_pseudo_slot(pin_id: int) -> int:
    return PIN_BASE - pin_id


def pin_id_of(pseudo_slot: int) -> int:
    return PIN_BASE - pseudo_slot


@dataclasses.dataclass
class Match:
    slot: int        # donor slot (engine-global index; < 0 = pinned row)
    shard: int       # DP shard both slots must live on
    n_tokens: int    # shareable prefix length (tokens, host-verified)

    @property
    def pinned(self) -> bool:
        return self.slot < 0


class _Node:
    __slots__ = ("children", "slots")

    def __init__(self):
        self.children: Dict[tuple, _Node] = {}
        self.slots: set = set()


class PrefixCache:
    """Radix trie of live prompt prefixes, one level per page.

    ``completed[slot]`` tracks how many prompt tokens of a slot are
    actually in device KV (fed through completed steps); matches never
    exceed it, so a donor mid-prefill only donates what it has written.
    Entries leave the trie when their request finishes — pages a sharer
    still maps stay alive through their refcount, and the sharer itself
    remains a donor for the common prefix.
    """

    def __init__(self, page_size: int):
        self.psz = int(page_size)
        self.roots: Dict[int, _Node] = {}
        self.tokens: Dict[int, List[int]] = {}
        self.shard_of: Dict[int, int] = {}
        self.completed: Dict[int, int] = {}
        #: set by the engine: the §13 Telemetry facade (trie hit/miss
        #: counters); None keeps the cache usable standalone
        self.telemetry = None

    # -- bookkeeping ----------------------------------------------------
    def _pages(self, tokens: Sequence[int]):
        for i in range(len(tokens) // self.psz):
            yield tuple(tokens[i * self.psz:(i + 1) * self.psz])

    def insert(self, slot: int, shard: int, tokens: Sequence[int]) -> None:
        self.tokens[slot] = list(tokens)
        self.shard_of[slot] = shard
        self.completed[slot] = 0
        node = self.roots.setdefault(shard, _Node())
        for key in self._pages(tokens):
            node = node.children.setdefault(key, _Node())
            node.slots.add(slot)

    def update_progress(self, slot: int, n_in_kv: int) -> None:
        if slot in self.completed:
            n = min(int(n_in_kv), len(self.tokens[slot]))
            self.completed[slot] = max(self.completed[slot], n)

    def remove(self, slot: int) -> None:
        tokens = self.tokens.pop(slot, None)
        if tokens is None:
            return
        shard = self.shard_of.pop(slot)
        self.completed.pop(slot, None)
        node = self.roots.get(shard)
        path = []
        for key in self._pages(tokens):
            child = node.children.get(key)
            if child is None:
                break
            child.slots.discard(slot)
            path.append((node, key, child))
            node = child
        for parent, key, child in reversed(path):   # prune empty branches
            if not child.slots and not child.children:
                del parent.children[key]

    def live_slots(self) -> int:
        return len(self.tokens)

    # -- pinned pseudo-slots --------------------------------------------
    def pin_insert(self, pin_id: int, shard: int,
                   tokens: Sequence[int]) -> None:
        """Register a pinned prefix as a donor.  ``tokens`` must be the
        exact whole-page prefix held by the pin row (the engine passes
        ``prompt[:n_pages * psz]``); it is fully resident by
        construction, so completion equals its length."""
        pseudo = pin_pseudo_slot(pin_id)
        self.insert(pseudo, shard, tokens)
        self.completed[pseudo] = len(tokens)

    def pin_remove(self, pin_id: int) -> None:
        self.remove(pin_pseudo_slot(pin_id))

    # -- matching -------------------------------------------------------
    def match(self, tokens: Sequence[int],
              shard: Optional[int] = None) -> Optional[Match]:
        """Longest shareable prefix of ``tokens`` among live prompts.

        Walks the trie page-by-page, then extends into the donor's
        partial page token-by-token.  The result is capped at the
        donor's completed (in-KV) length and at ``len(tokens) - 1`` —
        the final prompt token is always fed normally so the new slot
        has a live position to sample its first output from.  Returns
        None below one full page (a COW copy wouldn't pay for itself).

        ``shard`` restricts the search to that shard's sub-trie — the
        cross-host placement contract (DESIGN.md §9): page ids are
        private to a DP shard, so a donor on shard i is unusable by a
        request placed on shard j != i EVEN WHEN THE TOKEN KEY MATCHES
        EXACTLY.  The scheduler queries each admissible shard
        separately and places the request where its longest shard-local
        match lives; an unrestricted match (shard=None) is only a
        diagnostic (best across shards), never a sharing decision.
        """
        limit = len(tokens) - 1
        if limit < self.psz:
            return None
        best: Optional[Match] = None
        if shard is None:
            roots = self.roots.items()
        else:
            root = self.roots.get(shard)
            roots = [] if root is None else [(shard, root)]
        for shard, root in roots:
            depth_of: Dict[int, int] = {}       # slot -> deepest page match
            node = root
            for i, key in enumerate(self._pages(tokens)):
                node = node.children.get(key)
                if node is None:
                    break
                for s in node.slots:
                    depth_of[s] = i + 1
            for s, d in depth_of.items():
                ent = self.tokens[s]
                n = d * self.psz
                while n < len(tokens) and n < len(ent) and tokens[n] == ent[n]:
                    n += 1
                n = min(n, self.completed[s], limit)
                if (best is None or n > best.n_tokens
                        or (n == best.n_tokens and best.slot < 0 <= s)):
                    # ties prefer a live donor over a pinned row (same
                    # pages either way; live donors keep LRU honest)
                    best = Match(slot=s, shard=shard, n_tokens=n)
        if best is None or best.n_tokens < self.psz:
            if self.telemetry is not None:
                self.telemetry.inc("trie_misses")
            return None
        if self.telemetry is not None:
            self.telemetry.inc("trie_hits")
        return best


# ---------------------------------------------- speculative continuations

class SpeculationStore:
    """Continuation history of hot prefixes — the host-side draft model
    for speculative decode (DESIGN.md §10).

    A *key* is a whole-page prompt prefix (the same page-granular token
    keys the trie and the pin ledger use, so a pinned prefix and its
    recorded continuations age together).  Each completed request whose
    prompt starts with key pages records its **continuation** — prompt
    tail beyond the key plus every generated token — and a later slot
    sitting at context ``key + suffix`` drafts the next ``k`` tokens
    from the first recorded stream consistent with its suffix.  A
    prefix is "hot" exactly when some stream is recorded under it:
    drafting needs history, and history only exists for repeated
    traffic.

    Pure host bookkeeping: drafting never reads device state (the step
    keeps its single sync), and a wrong draft costs only the rejected
    lane's rolled-back pages.  Bounded: ``keep`` streams per key
    (newest win), ``max_keys`` keys (LRU).
    """

    def __init__(self, page_size: int, keep: int = 4, max_keys: int = 64,
                 ngram: int = 3, ewma_alpha: float = 0.3):
        self.psz = int(page_size)
        self.keep = int(keep)
        self.max_keys = int(max_keys)
        self.ngram = int(ngram)
        self.ewma_alpha = float(ewma_alpha)
        self.streams: Dict[tuple, List[tuple]] = {}
        self._accept: Dict[tuple, float] = {}   # per-key accept-rate EWMA
        self._lru: Dict[tuple, int] = {}
        self._clock = itertools.count()

    def key_of(self, prompt: Sequence[int]) -> Optional[tuple]:
        """The whole-page prefix key of a prompt (None below one page)."""
        n = (len(prompt) // self.psz) * self.psz
        return tuple(prompt[:n]) if n >= self.psz else None

    def record(self, key: tuple, continuation: Sequence[int]) -> None:
        rows = self.streams.setdefault(key, [])
        cont = tuple(continuation)
        if cont in rows:
            rows.remove(cont)
        rows.append(cont)                       # newest last (wins lookup)
        del rows[:-self.keep]
        self._lru[key] = next(self._clock)
        while len(self.streams) > self.max_keys:
            cold = min(self._lru, key=self._lru.get)
            del self.streams[cold], self._lru[cold]
            self._accept.pop(cold, None)

    # -- accept-rate EWMA (the engine's break-even gate reads this) -----
    def observe(self, key: tuple, drafted: int, accepted: int) -> None:
        """Fold one verified lane's accept fraction into the key's EWMA.

        Called by the engine on the step's status read; the gate in
        ``ServingEngine._gate_k`` compares this against the measured
        cost ratio before drafting the key again (DESIGN.md §12)."""
        if drafted <= 0:
            return
        r = min(max(accepted / drafted, 0.0), 1.0)
        prev = self._accept.get(key)
        self._accept[key] = r if prev is None else (
            (1.0 - self.ewma_alpha) * prev + self.ewma_alpha * r)

    def accept_rate(self, key: tuple) -> Optional[float]:
        """EWMA accept rate for a key, or None before any observation
        (the gate drafts unmeasured prefixes optimistically — the first
        verified lane seeds the EWMA)."""
        return self._accept.get(key)

    def draft(self, key: tuple, suffix: Sequence[int],
              k: int) -> List[int]:
        """Up to ``k`` draft tokens for a slot at context key+suffix.

        Exact-suffix replay first: the newest stream whose recorded
        continuation starts with the slot's whole suffix wins (recent
        traffic predicts recent traffic).  When no stream matches
        exactly, an n-gram fallback matches the suffix's last g tokens
        (g = ngram down to 1) ANYWHERE in a recorded stream and drafts
        what followed there — drafting extends beyond exact replay
        while the verify/rollback plane stays unchanged (a wrong draft
        still costs only the rejected lane's rolled-back pages).  An
        absent history drafts nothing — the slot simply decodes a
        width-1 lane that step.
        """
        if k <= 0:
            return []
        rows = self.streams.get(key)
        if not rows:
            return []
        suffix = tuple(suffix)
        n = len(suffix)
        for cont in reversed(rows):
            if cont[:n] == suffix and len(cont) > n:
                self._lru[key] = next(self._clock)
                return list(cont[n:n + k])
        # n-gram fallback: longest recent-gram match, newest stream
        # first, rightmost occurrence within a stream (most context)
        for g in range(min(self.ngram, n), 0, -1):
            tail = suffix[-g:]
            for cont in reversed(rows):
                for i in range(len(cont) - g, -1, -1):
                    if cont[i:i + g] == tail and i + g < len(cont):
                        self._lru[key] = next(self._clock)
                        return list(cont[i + g:i + g + k])
        return []

    # -- warm restart (serving/engine.py save_warm/restore_warm) --------
    def to_state(self) -> list:
        """JSON-able snapshot, LRU-coldest key first so ``load_state``'s
        re-recording reproduces the eviction order.  Carries the
        accept-rate EWMA so the break-even gate stays warm across
        restarts."""
        keys = sorted(self.streams, key=lambda k: self._lru[k])
        return [[[int(t) for t in k],
                 [[int(t) for t in c] for c in self.streams[k]],
                 self._accept.get(k)]
                for k in keys]

    def load_state(self, rows: list) -> None:
        self.streams.clear()
        self._lru.clear()
        self._accept.clear()
        for row in rows:
            key, conts = row[0], row[1]
            kt = tuple(int(t) for t in key)
            for c in conts:
                self.record(kt, tuple(int(t) for t in c))
            if len(row) > 2 and row[2] is not None:
                self._accept[kt] = float(row[2])


# --------------------------------------------------- pinned host ledger

class PinnedPrefixes:
    """Host-side ledger of cache-owned (pinned) prefixes.

    Pure bookkeeping — the pages themselves live behind the device pin
    table and the pool refcounts; this class answers the policy
    questions (which row is free, who is LRU, how many pages does the
    cache hold on shard d) the scheduler asks when it pins, evicts
    under the per-shard ``budget_pages``, or sheds pins on pool
    pressure.  pin_id = shard * rows_per_shard + row, globally unique
    and stable for a pin's lifetime.
    """

    def __init__(self, n_shards: int, rows_per_shard: int,
                 budget_pages: int):
        self.n_shards = n_shards
        self.npin = int(rows_per_shard)
        self.budget = int(budget_pages)
        self.entries: Dict[int, dict] = {}          # pin_id -> entry
        self.free_rows = {s: set(range(self.npin)) for s in range(n_shards)}
        self.by_key: Dict[Tuple[int, tuple], int] = {}
        self._clock = itertools.count()

    # -- queries --------------------------------------------------------
    def pages_on(self, shard: int) -> int:
        return sum(e["pages"] for e in self.entries.values()
                   if e["shard"] == shard)

    def total_pages(self) -> int:
        return sum(e["pages"] for e in self.entries.values())

    def lookup(self, shard: int, tokens: Sequence[int]) -> Optional[int]:
        return self.by_key.get((shard, tuple(tokens)))

    def lru(self, shard: int) -> Optional[int]:
        cands = [(e["used"], pid) for pid, e in self.entries.items()
                 if e["shard"] == shard]
        return min(cands)[1] if cands else None

    def fits(self, shard: int, pages: int) -> bool:
        return self.pages_on(shard) + pages <= self.budget

    # -- mutation -------------------------------------------------------
    def add(self, shard: int, tokens: Sequence[int], pages: int) -> int:
        row = min(self.free_rows[shard])            # caller checked free
        self.free_rows[shard].discard(row)
        pin_id = shard * self.npin + row
        self.entries[pin_id] = {"shard": shard, "row": row,
                                "tokens": tuple(tokens), "pages": pages,
                                "used": next(self._clock)}
        self.by_key[(shard, tuple(tokens))] = pin_id
        return pin_id

    def has_free_row(self, shard: int) -> bool:
        return bool(self.free_rows[shard])

    def remove(self, pin_id: int) -> Tuple[int, int]:
        e = self.entries.pop(pin_id)
        self.free_rows[e["shard"]].add(e["row"])
        self.by_key.pop((e["shard"], e["tokens"]), None)
        return e["shard"], e["row"]

    def touch(self, pin_id: int) -> None:
        if pin_id in self.entries:
            self.entries[pin_id]["used"] = next(self._clock)

    # -- warm restart / crash recovery ----------------------------------
    def to_state(self) -> list:
        """JSON-able ledger snapshot, LRU-coldest entry first (the
        journal's pin events and a warm save share this shape)."""
        ents = sorted(self.entries.items(), key=lambda kv: kv[1]["used"])
        return [{"pin_id": int(pid), "shard": int(e["shard"]),
                 "row": int(e["row"]),
                 "tokens": [int(t) for t in e["tokens"]],
                 "pages": int(e["pages"])} for pid, e in ents]

    def load_state(self, entries: list) -> None:
        """Rebuild the ledger at its exact rows — the device pin table
        being restored alongside references those rows, so a pin must
        come back where its pages already are."""
        self.entries.clear()
        self.by_key.clear()
        self.free_rows = {s: set(range(self.npin))
                          for s in range(self.n_shards)}
        for e in entries:                       # LRU-coldest first
            shard, row = int(e["shard"]), int(e["row"])
            toks = tuple(int(t) for t in e["tokens"])
            pid = shard * self.npin + row
            self.free_rows[shard].discard(row)
            self.entries[pid] = {"shard": shard, "row": row,
                                 "tokens": toks, "pages": int(e["pages"]),
                                 "used": next(self._clock)}
            self.by_key[(shard, toks)] = pid


# --------------------------------------------------------- device steps

def share_prefix_step(psz: int, state, dst_oh, src_oh, n_tokens,
                      axis_name=None):
    """Map ``n_tokens`` of the src slot's prefix into the dst slot.

    dst_oh / src_oh: bool[DP, Bl] one-hots on the SAME shard;
    n_tokens: int32 scalar (>= 1, host-capped at the donor's completed
    length and the page-table capacity).  Jitted once; called per
    admission-with-match, off the per-token path.

    ``axis_name`` names the mesh axis when the call runs under
    shard_map (DESIGN.md §9): all state mutation is dst-shard-local
    either way (the one-hots are False everywhere else), but the
    returned ``ok`` flag must be the dst shard's verdict on every host
    — one tiny psum replicates it (the call's only collective).

    Protocol (all-or-nothing, ``ok`` reports the outcome):
      1. full pages [0, n_tokens // psz) of the donor's table are
         copied into the dst row and each gains a reference;
      2. if the prefix ends mid-page, a fresh page is allocated from
         the SHARED pool (admission-time bulk, like prefill loading —
         never from the slot's private lane, whose >= ell stock is the
         §4.2 never-dry budget for the next chunk) and the donor's
         partial page is copied into it across every paged layer
         (copy-on-write at the first divergent append — the dst slot
         appends into its private copy, never into the shared page);
      3. seq_lens[dst] = n_tokens, so the engine feeds only the
         remaining prompt suffix.
    """
    src_row = jnp.sum(jnp.where(src_oh[..., None], state.page_tables, 0),
                      axis=(0, 1))                                 # [maxp]
    return _share_from_row(psz, state, dst_oh, src_row, n_tokens,
                           axis_name)


def share_pinned_step(psz: int, state, pin_tables, dst_oh, pin_oh,
                      n_tokens, axis_name=None):
    """:func:`share_prefix_step` with a pinned row as the donor.

    pin_oh: bool[DP, Npin] one-hot on the dst shard.  The pin row's
    pages are live (cache-owned refcount >= 1), their KV content is
    still resident, and the row is NULL beyond its pinned pages — so
    the shared-row protocol applies verbatim, including the COW copy
    when the match ends mid-page.
    """
    src_row = jnp.sum(jnp.where(pin_oh[..., None], pin_tables, 0),
                      axis=(0, 1))                                 # [maxp]
    return _share_from_row(psz, state, dst_oh, src_row, n_tokens,
                           axis_name)


def _share_from_row(psz: int, state, dst_oh, src_row, n_tokens,
                    axis_name=None):
    """Shared body: map a donor table row into the dst slot (see
    :func:`share_prefix_step` for the protocol)."""
    DP, Bl, maxp = state.page_tables.shape
    n_tokens = jnp.asarray(n_tokens, jnp.int32)
    fp = n_tokens // psz                          # full pages shared
    partial = n_tokens % psz                      # tokens in the COW page
    k = jnp.arange(maxp, dtype=jnp.int32)
    np_needed = (n_tokens + psz - 1) // psz
    donor_ok = src_row[jnp.clip(np_needed - 1, 0, maxp - 1)] >= 0
    shard_mask = jnp.any(dst_oh, axis=1)                           # [DP]

    # COW page for the partial tail, from the SHARED pool (off the hot
    # path; taking it from the slot's lane would eat into the lane's
    # never-dry stock and silently deny the slot's next chunk)
    want = dst_oh & (partial > 0) & donor_ok
    pool, fresh = classed_pool.alloc_from_shared_dp(
        state.pool, CLS_KV, want.astype(jnp.int32), 1)
    fresh = fresh[..., 0]                                          # [DP, Bl]
    ok = donor_ok & ((partial == 0) | jnp.any(fresh >= 0))

    # register the extra references on the donor's full pages
    shared_ids = jnp.where((k < fp) & ok, src_row, NULL)
    ids_dp = jnp.where(shard_mask[:, None], shared_ids[None, :], NULL)
    pool = classed_pool.addref_dp(pool, CLS_KV, ids_dp)

    # dst table row: donor's full pages, then the fresh partial copy
    row = jnp.where(k[None, None, :] < fp, src_row[None, None, :],
                    state.page_tables)
    row = jnp.where((k[None, None, :] == fp) & (partial > 0) &
                    (fresh[..., None] >= 0), fresh[..., None], row)
    page_tables = jnp.where(dst_oh[..., None] & ok, row, state.page_tables)

    # copy the donor's partial page into the fresh page (every layer)
    src_pid = jnp.maximum(src_row[jnp.clip(fp, 0, maxp - 1)], 0)
    fresh_shard = jnp.max(jnp.where(want, fresh, NULL), axis=1)    # [DP]
    any_pages = next(iter(state.kv_pages.values()))[0]
    P = any_pages.shape[2]
    tgt = jnp.where(shard_mask & ok & (partial > 0) & (fresh_shard >= 0),
                    fresh_shard, P)                                # P => drop

    def copy_pages(pages):                        # [S, DP, P, KH, psz, hd]
        def per_shard(pg, t):
            return pg.at[:, t].set(pg[:, src_pid], mode="drop")
        return jax.vmap(per_shard, in_axes=(1, 0), out_axes=1)(pages, tgt)

    kv_pages = {pos: (copy_pages(kp), copy_pages(vp))
                for pos, (kp, vp) in state.kv_pages.items()}
    seq_lens = jnp.where(dst_oh & ok, n_tokens, state.seq_lens)
    state = state._replace(kv_pages=kv_pages, page_tables=page_tables,
                           seq_lens=seq_lens, pool=pool)
    if axis_name is not None:
        # under shard_map each shard computed its own (meaningless off
        # the dst shard) ok; replicate the dst shard's verdict so the
        # host reads one truth — the call's only cross-shard traffic
        ok = jax.lax.psum(
            jnp.where(jnp.any(dst_oh), ok, False).astype(jnp.int32),
            axis_name) > 0
    return state, ok


def pin_prefix_step(pool, pin_tables, page_tables, pin_oh, src_oh,
                    n_pages):
    """Pin the first ``n_pages`` of a live slot's table into a pin row.

    pin_oh: bool[DP, Npin] one-hot naming the (free) destination row;
    src_oh: bool[DP, Bl] one-hot naming the live source slot, SAME
    shard; n_pages: int32 scalar >= 1 (whole pages only — a partial
    page is still being appended into and cannot be cache-owned).

    The cache takes ONE reference per pinned page
    (:func:`hier_pool.addref`): when the source slot later releases
    inside the jitted step, the pages drop to refcount 1 instead of 0
    and stay off the free stacks — alive, content intact, donatable.
    Jitted once; called per pin (prefill completion or preemption),
    off the per-token path.
    """
    DP, Npin, maxp = pin_tables.shape
    k = jnp.arange(maxp, dtype=jnp.int32)
    src_row = jnp.sum(jnp.where(src_oh[..., None], page_tables, 0),
                      axis=(0, 1))                                 # [maxp]
    row = jnp.where(k < jnp.asarray(n_pages, jnp.int32), src_row, NULL)
    shard_mask = jnp.any(pin_oh, axis=1)                           # [DP]
    ids_dp = jnp.where(shard_mask[:, None], row[None, :], NULL)
    pool = classed_pool.addref_dp(pool, CLS_KV, ids_dp)
    pin_tables = jnp.where(pin_oh[..., None], row[None, None, :],
                           pin_tables)
    return pool, pin_tables


def unpin_step(pool, pin_tables, pin_oh):
    """Evict pinned rows: drop the cache's references, clear the rows.

    pin_oh: bool[DP, Npin] (any number of rows, any shards).  Pages
    whose refcount reaches zero return to the shard's SHARED stack
    (:func:`hier_pool.free_shared` — pin rows belong to no lane; the
    per-step rebalance redistributes).  Pages a live sharer still maps
    just lose the cache's reference.
    """
    DP = pin_tables.shape[0]
    ids = jnp.where(pin_oh[..., None], pin_tables, NULL)
    pool = classed_pool.free_shared_dp(pool, CLS_KV, ids.reshape(DP, -1))
    pin_tables = jnp.where(pin_oh[..., None], NULL, pin_tables)
    return pool, pin_tables
