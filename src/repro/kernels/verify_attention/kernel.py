"""Pallas TPU verify-attention kernel: page-grouped block schedule.

The speculative verify step is the allocator-friendly shape the paper's
refcounted pool produces: many short draft lanes (1 committed + k draft
queries each) whose block tables point at the *same* physical prefix
pages — sharing that the int16 refcounts already made explicit when
`share_prefix_step` addref'd them.  The per-lane schedule of
`paged_attention_chunk` (grid (B, KH, maxp)) re-DMAs such a hot page
once per lane reading it; this kernel inverts the schedule so each hot
page crosses HBM once per adjacency group:

* Work items.  Host/jit side builds a flat list of (page, lane, slot)
  triples — one per resident in-causal-window block-table entry — and
  sorts it by physical page id (`build_verify_schedule`).  Lanes whose
  tables share a page therefore become *consecutive* grid steps.
* Grid = (KH, n_items) with the item axis innermost and sequential.
  The K/V BlockSpec index_map is driven by the scalar-prefetched sorted
  page ids, so consecutive items on the same page map to the same block
  index and Pallas's pipeline skips the re-DMA: one HBM read per hot
  page per kv-head, regardless of how many lanes share it.
* All lanes' queries stay VMEM-resident as one [B*T*G, hd] tile (the
  verify window is tiny: T = k+1 draft positions), with one online-
  softmax accumulator row per (lane, token, q-head).  Each item scores
  the page against every row and masks to its own lane; rows of other
  lanes see NEG_INF, which the running max either ignores (m already
  finite -> p underflows to 0) or later cancels (corr = exp(-inf) = 0
  on the first real key), the same self-correcting trick the chunk
  kernel uses for dead pages.
* Dead items (non-resident or fully beyond the causal window) sort to
  the tail with their page clamped to 0: they coalesce into one masked
  DMA instead of scattering reads.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def build_verify_schedule(page_table, base_lens, T: int, psz: int):
    """Sort the step's (page, lane, slot) work items by physical page.

    page_table: int32[B, maxp] (entries < 0 are dead); base_lens:
    int32[B] lane lengths before the verify window; T: verify width
    (k+1); psz: page size.  Returns (pages, lanes, slots), each
    int32[B * maxp], sorted ascending by page id with dead/out-of-window
    items (page == -1) at the tail.  Shared pages — the ones the
    refcounts count > 1 readers for — land adjacent, which is the whole
    scheduling trick.  The sort is stable, so equal pages keep lane
    order and the schedule is deterministic.
    """
    B, maxp = page_table.shape
    flat = page_table.reshape(-1).astype(jnp.int32)
    idx = jnp.arange(B * maxp, dtype=jnp.int32)
    lanes = idx // maxp
    slots = idx % maxp
    # a page whose first kv position is past the lane's last query
    # position (base + T - 1) contributes nothing
    needed = (flat >= 0) & (slots * psz <= base_lens[lanes] + T - 1)
    key = jnp.where(needed, flat, jnp.int32(2 ** 30))
    order = jnp.argsort(key)
    return (jnp.where(needed, flat, -1)[order],
            lanes[order], slots[order])


def _verify_kernel(pages_ref, lanes_ref, slots_ref,  # scalar-prefetch [NI]
                   q_ref,              # [B, 1, T*G, hd] (block for kh h)
                   k_ref,              # [1, psz, hd] page tile
                   v_ref,              # [1, psz, hd]
                   lens_ref,           # [B] verify-base lengths, in SMEM
                   o_ref,              # [B, 1, T*G, hd]
                   m_scr, l_scr, acc_scr,  # VMEM [B*T*G,1],[.,1],[.,hd]
                   *, psz: int, scale: float, G: int, TG: int):
    j = pl.program_id(1)
    n_items = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    page = pages_ref[j]
    lane = lanes_ref[j]
    slot = slots_ref[j]

    B = q_ref.shape[0]
    R = B * TG
    q = q_ref[:, 0].astype(jnp.float32).reshape(R, q_ref.shape[3])
    k = k_ref[0].astype(jnp.float32)                   # [psz, hd]
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # [R, psz]
    # row r = b*TG + t*G + g is query token t of lane b; only rows of
    # this item's lane may take this page, causally (kv <= base + t)
    row = jax.lax.broadcasted_iota(jnp.int32, (R, psz), 0)
    row_lane = row // TG
    row_t = (row % TG) // G
    kvpos = slot * psz + jax.lax.broadcasted_iota(jnp.int32, (R, psz), 1)
    valid = (row_lane == lane) & (page >= 0) & (kvpos <= lens_ref[lane] + row_t)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]                                # [R, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                             # [R, psz]
    corr = jnp.exp(m_prev - m_new)                     # [R, 1]
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # [R, hd]
    m_scr[...] = m_new

    @pl.when(j == n_items - 1)
    def _finish():
        # rows that never saw a valid key (ragged tail past a lane's
        # feed, or an idle slot) keep m == NEG_INF and must output zeros
        seen = m_scr[...] > NEG_INF * 0.5
        hd = o_ref.shape[3]
        out = jnp.where(seen, acc_scr[...] / jnp.maximum(l_scr[...], 1e-20),
                        0.0)
        o_ref[:, 0] = out.reshape(B, TG, hd).astype(o_ref.dtype)


def verify_attention(q, k_pages, v_pages, page_table, base_lens,
                     interpret: bool = False):
    """Page-grouped verify attention.

    q: [B, T, H, hd] — T = k+1 verify positions per lane; k/v_pages:
    [P, KH, psz, hd], head-major as the pool is stored (drafts' K/V
    already appended); table: [B, maxp];
    base_lens: int32[B] lengths before the verify window.  Bit-for-bit
    the same math as `verify_attention_ref` / `paged_attention_chunk`,
    only the page-visit order differs.
    """
    B, T, H, hd = q.shape
    P, KH, psz, _ = k_pages.shape
    maxp = page_table.shape[1]
    G = H // KH
    TG = T * G
    scale = 1.0 / (hd ** 0.5)

    pages, lanes, slots = build_verify_schedule(
        page_table.astype(jnp.int32), base_lens.astype(jnp.int32), T, psz)
    n_items = int(pages.shape[0])

    # [B, T, KH, G, hd] -> [B, KH, T*G, hd]: row r = t * G + g
    qg = q.reshape(B, T, KH, G, hd).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(B, KH, TG, hd)
    kp = k_pages.reshape(P * KH, psz, hd)      # tile p * KH + h
    vp = v_pages.reshape(P * KH, psz, hd)

    grid = (KH, n_items)

    def q_map(h, j, pages, lanes, slots):
        return (0, h, 0, 0)

    def kv_map(h, j, pages, lanes, slots):
        # consecutive items with the same page id produce the same block
        # index here — Pallas skips the re-DMA, which is the one-read-
        # per-hot-page property; dead items clamp to resident page 0
        return (jnp.maximum(pages[j], 0) * KH + h, 0, 0)

    out = pl.pallas_call(
        functools.partial(_verify_kernel, psz=psz, scale=scale, G=G, TG=TG),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((B, 1, TG, hd), q_map),
                pl.BlockSpec((1, psz, hd), kv_map),
                pl.BlockSpec((1, psz, hd), kv_map),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec((B, 1, TG, hd), q_map),
            scratch_shapes=[
                pltpu.VMEM((B * TG, 1), jnp.float32),
                pltpu.VMEM((B * TG, 1), jnp.float32),
                pltpu.VMEM((B * TG, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KH, TG, hd), q.dtype),
        interpret=interpret,
    )(pages, lanes, slots, qg, kp, vp, base_lens.astype(jnp.int32))
    out = out.reshape(B, KH, T, G, hd).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, T, H, hd)
