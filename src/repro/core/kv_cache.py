"""Paged KV cache built on the block pool — the allocator's main client.

vLLM-style paging adapted to TPU: the KV store is a pool of fixed-size
*pages* of ``page_size`` tokens; each sequence owns a page table (list of
page ids).  Appending a token is O(1) array ops; crossing a page
boundary allocates a page from the :mod:`hier_pool`/:mod:`block_pool`
(constant time, the paper's contribution).  Attention kernels read
through the page table (see ``repro.kernels.paged_attention``).

Layout choice for TPU: pages store K and V head-major,
``[num_pages, kv_heads, page_size, head_dim]``, so that a page of one
head is a contiguous (page_size x head_dim) VMEM tile, which the
kernels read in place — head_dim is kept a multiple of 128 by configs,
aligning the tiles with the MXU/VPU lanes.  A token's K or V is one
``head_dim`` row per head.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from . import block_pool
from .block_pool import BlockPool, NULL


class PagedKVCache(NamedTuple):
    pool: BlockPool           # page allocator
    k_pages: jax.Array        # [num_pages, kv_heads, page_size, head_dim]
    v_pages: jax.Array        # [num_pages, kv_heads, page_size, head_dim]
    page_tables: jax.Array    # int32[max_seqs, max_pages_per_seq]
    seq_lens: jax.Array       # int32[max_seqs] — tokens currently stored


def create(num_pages: int, page_size: int, kv_heads: int, head_dim: int,
           max_seqs: int, max_pages_per_seq: int,
           dtype=jnp.bfloat16) -> PagedKVCache:
    return PagedKVCache(
        pool=block_pool.create(num_pages),
        k_pages=jnp.zeros((num_pages, kv_heads, page_size, head_dim), dtype),
        v_pages=jnp.zeros((num_pages, kv_heads, page_size, head_dim), dtype),
        page_tables=jnp.full((max_seqs, max_pages_per_seq), NULL, jnp.int32),
        seq_lens=jnp.zeros((max_seqs,), jnp.int32),
    )


def page_size(cache: PagedKVCache) -> int:
    return cache.k_pages.shape[2]


def append(cache: PagedKVCache, k: jax.Array, v: jax.Array,
           active: jax.Array) -> Tuple["PagedKVCache", jax.Array]:
    """Append one token of K/V per active sequence.

    k, v: [max_seqs, kv_heads, head_dim]; active: bool[max_seqs].
    Returns (cache, ok[max_seqs]) — ok False if a page allocation failed.
    O(max_seqs) work, independent of cache size (paper's discipline).
    """
    S = cache.seq_lens.shape[0]
    psz = page_size(cache)
    pos_in_page = cache.seq_lens % psz
    page_idx = cache.seq_lens // psz

    needs_page = active & (pos_in_page == 0)
    pool, new_ids = block_pool.alloc(cache.pool, needs_page)
    ok = jnp.where(needs_page, new_ids >= 0, True) & active

    rows = jnp.arange(S)
    page_tables = cache.page_tables.at[rows, page_idx].set(
        jnp.where(needs_page & ok, new_ids,
                  cache.page_tables[rows, page_idx]))

    page_ids = page_tables[rows, page_idx]
    write = ok & (page_ids >= 0)
    # masked slots scatter to an out-of-range page and are dropped; the
    # previous read-modify-write idiom (write old value back to page 0)
    # raced with a real write to page 0 at the same position
    tgt = jnp.where(write, page_ids, cache.k_pages.shape[0])
    idx = (tgt[:, None], jnp.arange(k.shape[1]), pos_in_page[:, None])
    k_pages = cache.k_pages.at[idx].set(
        k.astype(cache.k_pages.dtype), mode="drop")
    v_pages = cache.v_pages.at[idx].set(
        v.astype(cache.v_pages.dtype), mode="drop")

    seq_lens = cache.seq_lens + write.astype(jnp.int32)
    return PagedKVCache(pool, k_pages, v_pages, page_tables, seq_lens), ok


def append_chunk(cache: PagedKVCache, k: jax.Array, v: jax.Array,
                 lens: jax.Array,
                 active: jax.Array | None = None
                 ) -> Tuple["PagedKVCache", jax.Array]:
    """Append up to C tokens of K/V per sequence in one fixed-shape call.

    k, v: [max_seqs, C, kv_heads, head_dim]; lens: int32[max_seqs] —
    tokens to append per sequence (0 <= lens[s] <= C); active:
    bool[max_seqs] (default all).  Pages for the whole chunk
    (ceil(C/psz) worst case per sequence) are taken from the pool in ONE
    :func:`block_pool.alloc_n` call, so cost stays O(max_seqs * C),
    independent of the pool size m.  Returns (cache, ok[max_seqs]) — ok
    False where the allocation was denied or the chunk would overflow
    the page table; denied sequences append nothing (all-or-nothing).
    """
    S, C = k.shape[0], k.shape[1]
    psz = page_size(cache)
    maxp = cache.page_tables.shape[1]
    num_pages = cache.k_pages.shape[0]
    if active is None:
        active = jnp.ones((S,), bool)
    L = cache.seq_lens
    n = jnp.where(active, jnp.clip(lens.astype(jnp.int32), 0, C), 0)
    asked = n
    n, pages_before, counts = block_pool.chunk_page_plan(L, n, psz, maxp)

    kmax = -(-C // psz)                            # ceil(C / psz), static
    pool, ids = block_pool.alloc_n(cache.pool, counts, kmax)
    ok = active & (n == asked) & block_pool.granted_mask(ids, counts)
    n = jnp.where(ok, n, 0)

    rows = jnp.arange(S)[:, None]
    kk = jnp.arange(kmax)[None, :]
    slot = pages_before[:, None] + kk
    new_page = (kk < counts[:, None]) & ok[:, None] & (ids >= 0)
    slot = jnp.where(new_page, slot, maxp)         # out-of-range => dropped
    page_tables = cache.page_tables.at[rows, slot].set(ids, mode="drop")

    t = jnp.arange(C)[None, :]
    pos = L[:, None] + t                           # [S, C] absolute positions
    write = t < n[:, None]
    pid = page_tables[rows, jnp.minimum(pos // psz, maxp - 1)]
    write = write & (pid >= 0)
    pid = jnp.where(write, pid, num_pages)         # out-of-range => dropped
    idx = (pid[..., None], jnp.arange(k.shape[2]), (pos % psz)[..., None])
    k_pages = cache.k_pages.at[idx].set(
        k.astype(cache.k_pages.dtype), mode="drop")
    v_pages = cache.v_pages.at[idx].set(
        v.astype(cache.v_pages.dtype), mode="drop")

    seq_lens = L + n
    return PagedKVCache(pool, k_pages, v_pages, page_tables, seq_lens), ok


def rollback(cache: PagedKVCache, n_tokens: jax.Array) -> PagedKVCache:
    """Un-append the last ``n_tokens[s]`` tokens of each sequence.

    The cache-level form of the serving step's speculative rollback
    (DESIGN.md §10): a rejected draft keeps its accepted prefix and
    returns exactly the whole-page over-allocation — pages that hold no
    remaining token — to the pool, in one fixed-shape release
    (:func:`block_pool.free` refcount semantics: a page another
    sequence still maps merely loses one reference).  The partial page
    the surviving prefix ends in stays mapped; its stale tail positions
    sit beyond ``seq_lens`` and are overwritten by the next append
    before any read can see them.  O(max_seqs * max_pages_per_seq),
    independent of num_pages.
    """
    S, P = cache.page_tables.shape
    psz = page_size(cache)
    n = jnp.clip(n_tokens.astype(jnp.int32), 0, cache.seq_lens)
    new_len = cache.seq_lens - n
    keep_pages = (new_len + psz - 1) // psz
    have_pages = (cache.seq_lens + psz - 1) // psz
    k = jnp.arange(P, dtype=jnp.int32)[None, :]
    roll = (k >= keep_pages[:, None]) & (k < have_pages[:, None])
    to_free = jnp.where(roll, cache.page_tables, NULL)
    pool = block_pool.free(cache.pool, to_free.reshape(-1))
    page_tables = jnp.where(roll, NULL, cache.page_tables)
    return PagedKVCache(pool, cache.k_pages, cache.v_pages,
                        page_tables, new_len)


def release(cache: PagedKVCache, seq_mask: jax.Array) -> PagedKVCache:
    """Release all pages of the masked sequences (one batch call).

    Each page loses one reference; pages still mapped by a
    prefix-sharing sibling stay live (release decrements instead of
    frees — :func:`block_pool.free`'s refcount semantics).
    O(max_seqs * max_pages_per_seq) scatter — independent of num_pages.
    """
    S, P = cache.page_tables.shape
    to_free = jnp.where(seq_mask[:, None], cache.page_tables, NULL)
    pool = block_pool.free(cache.pool, to_free.reshape(-1))
    page_tables = jnp.where(seq_mask[:, None], NULL, cache.page_tables)
    seq_lens = jnp.where(seq_mask, 0, cache.seq_lens)
    return PagedKVCache(pool, cache.k_pages, cache.v_pages,
                        page_tables, seq_lens)


def share_prefix(cache: PagedKVCache, dst: int, src: int,
                 n_tokens: jax.Array) -> Tuple["PagedKVCache", jax.Array]:
    """Map ``n_tokens`` of seq ``src``'s prefix into seq ``dst`` (static
    dst/src, traced n_tokens) — the refcount/COW protocol at the cache
    level (the serving engine runs the same protocol over the
    DecodeState's layer stack, see serving/prefix_cache.py).

    Full pages are shared: dst's table points at src's pages and each
    gains a reference (:func:`block_pool.addref`).  A partial tail page
    is copied-on-write: one fresh page from the pool, src's page
    content copied, so dst's first divergent append never touches the
    shared page.  seq_lens[dst] = n_tokens.  Returns (cache, ok) — ok
    False (nothing changed) if the COW allocation was denied or src's
    prefix is not resident.
    """
    psz = page_size(cache)
    maxp = cache.page_tables.shape[1]
    num_pages = cache.k_pages.shape[0]
    n_tokens = jnp.asarray(n_tokens, jnp.int32)
    fp = n_tokens // psz
    partial = n_tokens % psz
    src_row = cache.page_tables[src]
    np_needed = (n_tokens + psz - 1) // psz
    donor_ok = ((cache.seq_lens[src] >= n_tokens) &
                (src_row[jnp.clip(np_needed - 1, 0, maxp - 1)] >= 0))

    want = jnp.zeros((cache.seq_lens.shape[0],), bool).at[dst].set(
        (partial > 0) & donor_ok)
    pool, fresh = block_pool.alloc(cache.pool, want)
    fresh_id = fresh[dst]
    ok = donor_ok & ((partial == 0) | (fresh_id >= 0))

    k = jnp.arange(maxp, dtype=jnp.int32)
    shared_ids = jnp.where((k < fp) & ok, src_row, NULL)
    pool = block_pool.addref(pool, shared_ids)
    row = jnp.where(k < fp, src_row, cache.page_tables[dst])
    row = jnp.where((k == fp) & (partial > 0) & (fresh_id >= 0),
                    fresh_id, row)
    page_tables = cache.page_tables.at[dst].set(
        jnp.where(ok, row, cache.page_tables[dst]))

    src_pid = jnp.maximum(src_row[jnp.clip(fp, 0, maxp - 1)], 0)
    tgt = jnp.where(ok & (partial > 0) & (fresh_id >= 0), fresh_id,
                    num_pages)                     # out-of-range => drop
    k_pages = cache.k_pages.at[tgt].set(cache.k_pages[src_pid], mode="drop")
    v_pages = cache.v_pages.at[tgt].set(cache.v_pages[src_pid], mode="drop")
    seq_lens = cache.seq_lens.at[dst].set(
        jnp.where(ok, n_tokens, cache.seq_lens[dst]))
    return PagedKVCache(pool, k_pages, v_pages, page_tables, seq_lens), ok


def gather_kv(cache: PagedKVCache, seq_id: int | jax.Array,
              max_len: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Materialize a sequence's K/V up to max_len (reference path / tests):
    token-major k, v of [n_pages * psz, kv_heads, head_dim] and a valid mask.

    Production attention reads pages directly via the kernel; this is the
    jnp oracle used by ref implementations and the CPU dry-run path.
    """
    psz = page_size(cache)
    n_pages = -(-max_len // psz)   # round UP: a partial page still holds
    table = jax.lax.dynamic_slice(  # live tokens; masking trims the tail
        cache.page_tables, (seq_id, 0), (1, n_pages))[0]
    safe = jnp.maximum(table, 0)
    kh, hd = cache.k_pages.shape[1], cache.k_pages.shape[3]
    # [n_pages, KH, psz, hd] -> token-major [n_pages * psz, KH, hd]
    k = jnp.swapaxes(cache.k_pages[safe], 1, 2).reshape(n_pages * psz, kh, hd)
    v = jnp.swapaxes(cache.v_pages[safe], 1, 2).reshape(n_pages * psz, kh, hd)
    pos = jnp.arange(n_pages * psz)
    valid = ((pos < cache.seq_lens[seq_id]) & (pos < max_len)
             & jnp.repeat(table >= 0, psz))
    return k, v, valid
