"""Pure-jnp oracle for speculative verify attention.

Semantically verify attention *is* chunked paged attention: every lane
carries 1 committed token + k draft tokens at positions base..base+k,
each attending causally to the lane's resident pages.  The oracle states
that contract independently of the Pallas schedule (which reorders the
page visits to read shared pages once; see kernel.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def verify_attention_ref(q, k_pages, v_pages, page_table, base_lens):
    """q: [B, T, H, hd]; k/v_pages: [P, KH, psz, hd]; table: [B, maxp];
    base_lens: int32[B] sequence lengths BEFORE the verify window.

    Query token t of lane b sits at absolute position base_lens[b] + t
    (the drafts' K/V are already appended to the pages) and attends to
    kv positions <= base_lens[b] + t on resident pages.  Rows past a
    lane's live feed return zeros (all-masked softmax is guarded) so the
    engine can ragged-mask afterwards.
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
