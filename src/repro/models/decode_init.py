"""Decode-state construction: empty state + prefill-cache loading.

Host-side engine utilities (not jitted): the serving engine allocates
prompt pages through the paper's allocator and scatters prefill K/V into
them.  Layouts match :class:`repro.models.transformer.DecodeState`.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..core import classed_pool
from ..core.classed_pool import CLS_KV, ClassSpec
from .transformer import DecodeState, decode_state_defs, _positions


def empty_decode_state(cfg, dp: int, b_local: int, max_len: int,
                       chunk: int | None = None,
                       size_classes: int = 1,
                       expert_budget: int | None = None) -> DecodeState:
    """Concrete zero state; pages live in a per-shard size-classed
    two-level pool vector with one private lane per slot per class
    (``chunk`` sizes the KV lane batch ``ell`` — see
    :func:`repro.models.transformer.pool_ell`; ``size_classes`` sets
    the class vector — see :func:`~repro.models.transformer.
    pool_class_specs`; ``expert_budget`` sizes the CLS_EXPERT class
    when ``size_classes >= 3``)."""
    defs = decode_state_defs(cfg, dp, b_local, max_len, chunk=chunk,
                             size_classes=size_classes,
                             expert_budget=expert_budget)

    def zeros(sds):
        return jnp.zeros(sds.shape, sds.dtype)

    kv_pages = jax.tree.map(zeros, defs.kv_pages)
    rings = jax.tree.map(zeros, defs.rings)
    rec = jax.tree.map(zeros, defs.rec)
    specs = tuple(
        ClassSpec(page_size=0,                    # granularity not stored
                  num_blocks=hp.shared.free_ids.shape[1],
                  num_lanes=hp.private_top.shape[1],
                  ell=hp.private_ids.shape[2] // 3)
        for hp in defs.pool.classes)
    pool = classed_pool.create_dp(dp, specs)
    page_tables = jnp.full(defs.page_tables.shape, -1, jnp.int32)
    seq_lens = jnp.zeros(defs.seq_lens.shape, jnp.int32)
    enc_kv = jax.tree.map(zeros, defs.enc_kv) if defs.enc_kv is not None else None
    state_tables = None
    if defs.state_tables is not None:
        state_tables = jnp.full(defs.state_tables.shape, -1, jnp.int32)
    expert_pages = expert_tables = None
    if defs.expert_pages is not None:
        expert_pages = zeros(defs.expert_pages)
        expert_tables = jax.tree.map(
            lambda s: jnp.full(s.shape, -1, jnp.int32), defs.expert_tables)
    return DecodeState(kv_pages, rings, rec, page_tables, seq_lens,
                       pool, enc_kv, state_tables, expert_pages,
                       expert_tables)


def empty_serve_arrays(dp: int, b_local: int):
    """Device-resident per-slot serving registers: (last_tok, out_count,
    budget), all int32[dp, b_local] zeros.

    last_tok feeds the next decode step without a host round-trip;
    out_count/budget drive on-device done-detection (see
    serving.engine._serve_step).  The engine writes budget/out_count at
    admission (host->device set, off the sync path) and the jitted step
    owns them afterwards.
    """
    z = jnp.zeros((dp, b_local), jnp.int32)
    return z, z, z


def load_prefill(cfg, state: DecodeState, caches: Dict[str, Any],
                 prompt_len: int) -> DecodeState:
    """Scatter dense prefill caches into the paged/ring/recurrent state.

    caches: output of ``forward_prefill`` — attention caches are dense
    (k, v) of [n_groups, B, S, KH, hd]; recurrent caches are final
    states.  All B sequences share prompt_len.  Pages come straight
    from each shard's shared pool in one batched
    :func:`hier_pool.alloc_from_shared` grant (bulk admission — a whole
    prompt never fits a 3*ell lane, and this path is off the per-token
    hot path by construction).
    """
    dp, b_local, max_pages = state.page_tables.shape
    psz = cfg.page_size
    n_pages = (prompt_len + psz - 1) // psz
    assert n_pages <= max_pages
    st_kinds = _positions(cfg)

    def split_cache(pos):
        c = caches[pos]
        if cfg.arch_kind == "encdec":
            return c[0]     # (self_cache, cross_kv)
        return c

    def cross_kv(pos):
        return caches[pos][1]

    # --- page allocation: one batched shared-pool grant per shard
    counts = jnp.full((dp, b_local), n_pages, jnp.int32)
    pool, ids = classed_pool.alloc_from_shared_dp(
        state.pool, CLS_KV, counts, max(n_pages, 1))
    assert bool(jnp.all(ids[..., :n_pages] >= 0)), "prefill pool exhausted"
    tables = np.full((dp, b_local, max_pages), -1, np.int32)
    tables[:, :, :n_pages] = np.asarray(ids)[:, :, :n_pages]

    new_kv_pages = {}
    for pos, (kp, vp) in state.kv_pages.items():
        kd, vd = split_cache(pos)                 # [n, B, S, KH, hd]
        n, B, S, KH, hd = kd.shape
        kd = np.asarray(kd).reshape(n, dp, b_local, S, KH, hd)
        vd = np.asarray(vd).reshape(n, dp, b_local, S, KH, hd)
        kp = np.asarray(kp).copy()
        vp = np.asarray(vp).copy()
        pad = n_pages * psz - prompt_len
        if pad:
            z = np.zeros((n, dp, b_local, pad, KH, hd), kd.dtype)
            kd = np.concatenate([kd, z], axis=3)
            vd = np.concatenate([vd, z], axis=3)
        # token-major cache -> head-major pages [.., n_pages, KH, psz, hd]
        kd = kd.reshape(n, dp, b_local, n_pages, psz, KH, hd).swapaxes(4, 5)
        vd = vd.reshape(n, dp, b_local, n_pages, psz, KH, hd).swapaxes(4, 5)
        for d in range(dp):
            for b in range(b_local):
                pids = tables[d, b, :n_pages]
                kp[:, d, pids] = kd[:, d, b]
                vp[:, d, pids] = vd[:, d, b]
        new_kv_pages[pos] = (jnp.asarray(kp), jnp.asarray(vp))

    new_rings = {}
    for pos, (kr, vr) in state.rings.items():
        kd, vd = split_cache(pos)
        n, B, S, KH, hd = kd.shape
        W = kr.shape[3]
        kd = np.asarray(kd).reshape(n, dp, b_local, S, KH, hd)
        vd = np.asarray(vd).reshape(n, dp, b_local, S, KH, hd)
        krn = np.asarray(kr).copy()
        vrn = np.asarray(vr).copy()
        take = min(W, prompt_len)
        src = np.arange(prompt_len - take, prompt_len)
        for s in src:
            krn[:, :, :, s % W] = kd[:, :, :, s]
            vrn[:, :, :, s % W] = vd[:, :, :, s]
        new_rings[pos] = (jnp.asarray(krn), jnp.asarray(vrn))

    new_rec = {}
    for pos, st in state.rec.items():
        c = split_cache(pos)                       # {"h": [n,B,...], "conv":}
        new_rec[pos] = {
            "h": jnp.asarray(np.asarray(c["h"]).reshape(st["h"].shape)),
            "conv": jnp.asarray(np.asarray(c["conv"]).reshape(st["conv"].shape)),
        }

    enc_kv = state.enc_kv
    if cfg.arch_kind == "encdec":
        ks, vs = [], []
        order = [f"pos{j}" for j in range(len(cfg.pattern))]
        rem = [f"rem{j}" for j in range(len(cfg.remainder))]
        for pos in order:
            k, v = cross_kv(pos)                  # [n_groups, B, L, KH, hd]
            ks.append(np.asarray(k))
            vs.append(np.asarray(v))
        # interleave pattern positions back into layer order
        n_layers_grp = len(order) * cfg.n_groups
        kcat = np.stack(ks, axis=1).reshape(n_layers_grp, *ks[0].shape[1:])
        vcat = np.stack(vs, axis=1).reshape(n_layers_grp, *vs[0].shape[1:])
        for pos in rem:
            k, v = cross_kv(pos)
            kcat = np.concatenate([kcat, np.asarray(k)], axis=0)
            vcat = np.concatenate([vcat, np.asarray(v)], axis=0)
        L = kcat.shape[0]
        kcat = kcat.reshape(L, dp, b_local, *kcat.shape[2:])
        vcat = vcat.reshape(L, dp, b_local, *vcat.shape[2:])
        enc_kv = (jnp.asarray(kcat), jnp.asarray(vcat))

    return DecodeState(
        kv_pages=new_kv_pages, rings=new_rings, rec=new_rec,
        page_tables=jnp.asarray(tables),
        seq_lens=jnp.full((dp, b_local), prompt_len, jnp.int32),
        pool=pool,
        enc_kv=enc_kv,
        state_tables=state.state_tables)
