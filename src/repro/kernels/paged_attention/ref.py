"""Pure-jnp oracle for paged decode attention."""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def paged_attention_chunk_ref(q, k_pages, v_pages, page_table, base_lens):
    """Chunked-prefill oracle.  q: [B, T, H, hd]; base_lens: int32[B].

    Query token t of sequence b sits at absolute position base_lens[b] +
    t (its K/V — and those of every earlier chunk token — are already in
    the pages); it attends causally to kv positions <= base_lens[b] + t.
    Rows past a sequence's live chunk length return zeros (all-masked
    softmax is guarded), so callers can ragged-mask afterwards.
    """
    B, T, H, hd = q.shape
    P, KH, psz, _ = k_pages.shape
    maxp = page_table.shape[1]
    L = maxp * psz
    safe = jnp.maximum(page_table, 0)
    # [B, maxp, KH, psz, hd] -> token-major [B, L, KH, hd]
    k = jnp.swapaxes(k_pages[safe], 2, 3).reshape(B, L, KH, hd)
    v = jnp.swapaxes(v_pages[safe], 2, 3).reshape(B, L, KH, hd)
    if KH != H:
        k = jnp.repeat(k, H // KH, axis=2)
        v = jnp.repeat(v, H // KH, axis=2)
    kvpos = jnp.arange(L)
    qpos = base_lens[:, None] + jnp.arange(T)[None, :]          # [B, T]
    resident = jnp.repeat(page_table >= 0, psz, axis=1)         # [B, L]
    valid = (kvpos[None, None, :] <= qpos[:, :, None]) & resident[:, None, :]
    s = jnp.einsum("bthd,bkhd->bhtk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (hd ** 0.5)
    s = jnp.where(valid[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.any(valid[:, None], axis=-1, keepdims=True), p, 0.0)
    o = jnp.einsum("bhtk,bkhd->bthd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def paged_attention_ref(q, k_pages, v_pages, page_table, seq_lens):
    """q: [B, H, hd]; pages: [P, KH, psz, hd]; table: [B, maxp]; lens: [B].

    GQA: H q-heads read from KH kv-heads (H % KH == 0).
    """
    B, H, hd = q.shape
    P, KH, psz, _ = k_pages.shape
    maxp = page_table.shape[1]
    L = maxp * psz
    safe = jnp.maximum(page_table, 0)
    # [B, maxp, KH, psz, hd] -> token-major [B, L, KH, hd]
    k = jnp.swapaxes(k_pages[safe], 2, 3).reshape(B, L, KH, hd)
    v = jnp.swapaxes(v_pages[safe], 2, 3).reshape(B, L, KH, hd)
    if KH != H:
        k = jnp.repeat(k, H // KH, axis=2)
        v = jnp.repeat(v, H // KH, axis=2)
    pos = jnp.arange(L)
    valid = (pos[None] < seq_lens[:, None]) & jnp.repeat(
        page_table >= 0, psz, axis=1)
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (hd ** 0.5)
    s = jnp.where(valid[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bkhd->bhd", p, v.astype(jnp.float32)).astype(q.dtype)
