"""Pallas TPU paged decode-attention kernel.

Design (vLLM PagedAttention re-tiled for TPU; see DESIGN.md §2b):

* grid = (B, KH, max_pages); the page axis is innermost and sequential,
  so the online-softmax accumulator lives in VMEM scratch across pages.
* The block table is **scalar-prefetched** (pltpu.PrefetchScalarGridSpec)
  and drives the K/V page BlockSpec index_maps: page i of sequence b is
  DMA'd from HBM page ``table[b, i]`` — the block-table indirection of
  the paper's allocator, performed by the memory system, not by gathers.
* The KV pool is stored head-major, [P, KH, psz, hd], so a page of one
  kv head is a contiguous [psz, hd] tile (hd padded to 128 lanes by
  config).  The wrappers take that pool as it is stored and only merge
  its leading dims (a free reshape): nothing is copied per call; all
  q-heads of one kv-head group (GQA) are processed together as a
  [G, hd] tile (G = H // KH), so the MXU sees [G, hd] x [hd, psz].
* Out-of-range pages (table[b, i] < 0) are skipped by masking; dead DMA
  is avoided by clamping the index to 0 (a resident page) — the mask
  removes its contribution.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(table_ref,            # scalar-prefetch: [B, maxp]
            q_ref,                # [1, G, hd]   (block for (b, kh))
            k_ref,                # [1, psz, hd] page tile
            v_ref,                # [1, psz, hd]
            lens_ref,             # [B] in SMEM
            o_ref,                # [1, G, hd]
            m_scr, l_scr, acc_scr,  # VMEM scratch [G,1],[G,1],[G,hd]
            *, psz: int, scale: float):
    b = pl.program_id(0)
    i = pl.program_id(2)
    n_pages = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    page_id = table_ref[b, i]
    seq_len = lens_ref[b]
    base = i * psz

    q = q_ref[0, 0].astype(jnp.float32)                # [G, hd]
    k = k_ref[0].astype(jnp.float32)                   # [psz, hd]
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # [G, psz]
    pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, psz), 1)
    valid = (pos < seq_len) & (page_id >= 0)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]                                # [G, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                             # [G, psz]
    corr = jnp.exp(m_prev - m_new)                     # [G, 1]
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # [G, hd]
    m_scr[...] = m_new

    @pl.when(i == n_pages - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-20)).astype(o_ref.dtype)


def _chunk_kernel(table_ref,          # scalar-prefetch: [B, maxp]
                  q_ref,              # [1, 1, T*G, hd] (block for (b, kh))
                  k_ref,              # [1, psz, hd] page tile
                  v_ref,              # [1, psz, hd]
                  lens_ref,           # [B] chunk-base lengths, in SMEM
                  o_ref,              # [1, 1, T*G, hd]
                  m_scr, l_scr, acc_scr,  # VMEM scratch [R,1],[R,1],[R,hd]
                  *, psz: int, scale: float, G: int):
    b = pl.program_id(0)
    i = pl.program_id(2)
    n_pages = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    page_id = table_ref[b, i]
    base = lens_ref[b]

    R = q_ref.shape[2]                                 # T * G rows
    q = q_ref[0, 0].astype(jnp.float32)                # [R, hd]
    k = k_ref[0].astype(jnp.float32)                   # [psz, hd]
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # [R, psz]
    # row r = t * G + g is query token t; it may see kv pos <= base + t
    qpos = base + jax.lax.broadcasted_iota(jnp.int32, (R, psz), 0) // G
    kvpos = i * psz + jax.lax.broadcasted_iota(jnp.int32, (R, psz), 1)
    valid = (kvpos <= qpos) & (page_id >= 0)
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

    @pl.when(i == n_pages - 1)
    def _finish():
        # rows that never saw a valid key (idle slot: page table all -1,
        # or a ragged tail past the live chunk) keep m == NEG_INF; they
        # must output zeros like the ref, not a mean of masked V (the
        # masked scores are a *finite* -1e30, so p = exp(s - m) = 1)
        seen = m_scr[...] > NEG_INF * 0.5
        o_ref[0, 0] = jnp.where(
            seen, acc_scr[...] / jnp.maximum(l_scr[...], 1e-20),
            0.0).astype(o_ref.dtype)


def paged_attention_chunk(q, k_pages, v_pages, page_table, base_lens,
                          interpret: bool = False):
    """Chunked-prefill paged attention.

    q: [B, T, H, hd] — T new tokens per sequence, causally masked within
    the chunk; k/v_pages: [P, KH, psz, hd] (the chunk's K/V already
    appended); table: [B, maxp]; base_lens: int32[B] sequence lengths
    BEFORE the chunk.  Same scalar-prefetched block-table indirection as
    the decode kernel; the q tile packs all chunk tokens of one GQA
    group as [T*G, hd] rows so the MXU sees [T*G, hd] x [hd, psz].
    """
    B, T, H, hd = q.shape
    P, KH, psz, _ = k_pages.shape
    maxp = page_table.shape[1]
    G = H // KH
    scale = 1.0 / (hd ** 0.5)

    # [B, T, KH, G, hd] -> [B, KH, T*G, hd]: row r = t * G + g
    qg = q.reshape(B, T, KH, G, hd).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(B, KH, T * G, hd)
    kp = k_pages.reshape(P * KH, psz, hd)      # tile p * KH + h
    vp = v_pages.reshape(P * KH, psz, hd)

    grid = (B, KH, maxp)

    def q_map(b, h, i, tbl):
        return (b, h, 0, 0)

    def kv_map(b, h, i, tbl):
        return (jnp.maximum(tbl[b, i], 0) * KH + h, 0, 0)

    out = pl.pallas_call(
        functools.partial(_chunk_kernel, psz=psz, scale=scale, G=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, T * G, hd), q_map),
                pl.BlockSpec((1, psz, hd), kv_map),
                pl.BlockSpec((1, psz, hd), kv_map),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec((1, 1, T * G, hd), q_map),
            scratch_shapes=[
                pltpu.VMEM((T * G, 1), jnp.float32),
                pltpu.VMEM((T * G, 1), jnp.float32),
                pltpu.VMEM((T * G, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KH, T * G, hd), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), qg, kp, vp,
      base_lens.astype(jnp.int32))
    out = out.reshape(B, KH, T, G, hd).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, T, H, hd)


def paged_attention(q, k_pages, v_pages, page_table, seq_lens,
                    interpret: bool = False):
    """q: [B, H, hd]; k/v_pages: [P, KH, psz, hd]; table: [B, maxp]."""
    B, H, hd = q.shape
    P, KH, psz, _ = k_pages.shape
    maxp = page_table.shape[1]
    G = H // KH
    scale = 1.0 / (hd ** 0.5)

    # group q by kv head -> [B, KH, G, hd]; pages to [P*KH, psz, hd] tiles
    qg = q.reshape(B, KH, G, hd)
    kp = k_pages.reshape(P * KH, psz, hd)
    vp = v_pages.reshape(P * KH, psz, hd)

    grid = (B, KH, maxp)

    def q_map(b, h, i, tbl):
        return (b, h, 0, 0)

    def kv_map(b, h, i, tbl):
        # clamp dead table entries to page 0 (resident); the in-kernel
        # mask (page_id < 0) zeroes their contribution
        return (jnp.maximum(tbl[b, i], 0) * KH + h, 0, 0)

    def o_map(b, h, i, tbl):
        return (b, h, 0, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, psz=psz, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, hd), q_map),
                pl.BlockSpec((1, psz, hd), kv_map),
                pl.BlockSpec((1, psz, hd), kv_map),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec((1, 1, G, hd), o_map),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KH, G, hd), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), qg, kp, vp,
      seq_lens.astype(jnp.int32))
    return out.reshape(B, H, hd)
