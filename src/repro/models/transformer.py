"""Unified decoder stack: dense / GQA / SWA / local-global / MoE / SSD /
RG-LRU / enc-dec / VLM — one scan-over-pattern-groups implementation.

Layer layout comes from ``cfg.pattern`` (see configs/base.py).  Parameters
for the ``n_groups`` full pattern repetitions are stacked on a leading
"layers" axis and consumed by ``jax.lax.scan`` — compile time and HLO
size are independent of depth.  Remainder layers (when n_layers is not a
multiple of the pattern length) are unrolled once.

Three entry points:
  * :func:`forward_train`        — full-sequence, no caches (training loss)
  * :func:`forward_prefill`      — full-sequence, fills the decode state
  * :func:`forward_decode_chunk` — a variable-width token lane (up to T
    tokens per sequence) against the decode state; single-token decode
    is simply a width-1 lane (the only decode entry point — the old
    ``forward_decode`` single-token path is gone)

Decode state (the paper's technique lives here):
  * global-attention layers use **paged KV** (block tables +
    fixed-size pages from the two-level :mod:`repro.core.hier_pool` —
    constant-time alloc/free from per-*slot* private lanes exactly like
    the paper's private pools, with the shared pool behind them and the
    deamortized ``rebalance`` once per engine step);
  * local/SWA layers use fixed-size **ring slabs** (bounded state needs
    no paging — it is a fixed-size block handed out at admission);
  * SSD / RG-LRU layers carry fixed-size recurrent state slabs.

Decode batch layout is [DP, B_local, ...] with DP sharded over the data
(and pod) mesh axes; every cache gather/scatter is vmapped over DP so
page ids stay shard-local (no cross-shard gathers — see DESIGN.md §5).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import base_kind, is_moe_kind
from ..core import block_pool, classed_pool, hier_pool
from ..core.classed_pool import CLS_KV, ClassSpec
from ..kernels.paged_attention.ops import paged_attention_chunk
from ..kernels.verify_attention.ops import verify_attention
from ..parallel.partition import constrain_batch
from ..serving.trace import scope, scoped
from . import attention as attn
from . import moe as moe_mod
from . import rglru as rglru_mod
from . import ssm as ssm_mod
from .layers import (ParamDef, apply_norm, apply_rope, embed_apply,
                     embed_defs, ffn_apply, ffn_defs, norm_defs)


# ===================================================================== defs

def _stack(defs, n: int):
    return jax.tree.map(
        lambda d: ParamDef((n,) + d.shape, ("layers",) + d.axes, d.dtype, d.init),
        defs, is_leaf=lambda x: isinstance(x, ParamDef))


def layer_defs(cfg, kind: str, with_xattn: bool = False) -> Dict[str, Any]:
    bk = base_kind(kind)
    d: Dict[str, Any] = {"norm1": norm_defs(cfg)}
    if bk in ("global", "local"):
        d["attn"] = attn.attn_defs(cfg)
    elif bk == "ssd":
        d["ssd"] = ssm_mod.ssd_defs(cfg)
    elif bk == "rglru":
        d["rglru"] = rglru_mod.rglru_defs(cfg)
    else:
        raise ValueError(kind)
    if with_xattn:
        d["norm_x"] = norm_defs(cfg)
        d["xattn"] = attn.attn_defs(cfg)
    if bk != "ssd" and cfg.d_ff:
        d["norm2"] = norm_defs(cfg)
        d["ffn"] = moe_mod.moe_defs(cfg) if is_moe_kind(kind) else ffn_defs(cfg)
    return d


def model_defs(cfg) -> Dict[str, Any]:
    is_dec_of_encdec = cfg.arch_kind == "encdec"
    defs: Dict[str, Any] = {"embed": embed_defs(cfg)}
    fn = norm_defs(cfg)
    if fn:
        defs["final_norm"] = fn
    group = {f"pos{j}": layer_defs(cfg, k, with_xattn=is_dec_of_encdec)
             for j, k in enumerate(cfg.pattern)}
    if cfg.n_groups:
        defs["groups"] = _stack(group, cfg.n_groups)
    if cfg.remainder:
        defs["rem"] = {
            f"pos{j}": layer_defs(cfg, k, with_xattn=is_dec_of_encdec)
            for j, k in enumerate(cfg.remainder)}
    if is_dec_of_encdec:
        enc_layer = layer_defs(cfg, "global")
        defs["encoder"] = _stack(enc_layer, cfg.enc_layers)
        if fn:
            defs["enc_final_norm"] = norm_defs(cfg)
    return defs


# ================================================================ train path

def _mix_train(cfg, lp, x, kind, enc_out=None, causal=True):
    """One layer (full sequence).  Returns (x, cache) where cache is
    (k, v) for attention layers or the final recurrent state tree."""
    kind = base_kind(kind)
    h = apply_norm(cfg, lp["norm1"], x)
    if kind in ("global", "local"):
        o, cache = attn.attention_train(cfg, lp["attn"], h, kind, causal=causal)
    elif kind == "ssd":
        o, (hn, cn) = ssm_mod.ssd_block_apply(cfg, lp["ssd"], h)
        cache = {"h": hn, "conv": cn}
    else:
        o, (hn, cn) = rglru_mod.rglru_block_apply(cfg, lp["rglru"], h)
        cache = {"h": hn, "conv": cn}
    x = x + o
    if "xattn" in lp and enc_out is not None:
        hx = apply_norm(cfg, lp["norm_x"], x)
        k = jnp.einsum("bsd,dhk->bshk", enc_out, lp["xattn"]["wk"])
        v = jnp.einsum("bsd,dhk->bshk", enc_out, lp["xattn"]["wv"])
        x = x + attn.cross_attention(cfg, lp["xattn"], hx, (k, v))
        cache = (cache, (k, v))
    if "ffn" in lp:
        h2 = apply_norm(cfg, lp["norm2"], x)
        f = (moe_mod.moe_apply(cfg, lp["ffn"], h2) if "router" in lp["ffn"]
             else ffn_apply(cfg, lp["ffn"], h2))
        x = x + f
    return x, cache


def _encoder_apply(cfg, params, enc_embeds):
    """Whisper-style encoder over stubbed frame embeddings."""
    @functools.partial(jax.checkpoint, prevent_cse=False)
    def body(x, lp):
        x = constrain_batch(x)
        x, _ = _mix_train(cfg, lp, x, "global", causal=False)
        return x, None
    x, _ = jax.lax.scan(body, enc_embeds, params["encoder"])
    if "enc_final_norm" in params:
        x = apply_norm(cfg, params["enc_final_norm"], x)
    return x


def forward_train(cfg, params, tokens, extra: Optional[Dict] = None,
                  remat: bool = True):
    """tokens [B, S] (+ extra embeds for vlm/encdec) -> hidden [B, S', d]."""
    x = embed_apply(params["embed"], tokens).astype(cfg.jdtype)
    if cfg.arch_kind == "vlm" and extra and "img_embeds" in extra:
        x = jnp.concatenate(
            [extra["img_embeds"].astype(cfg.jdtype), x], axis=1)
    x = constrain_batch(x)
    enc_out = None
    if cfg.arch_kind == "encdec":
        enc_out = _encoder_apply(
            cfg, params, extra["enc_embeds"].astype(cfg.jdtype))
        enc_out = constrain_batch(enc_out)

    def group_body(x, gparams):
        x = constrain_batch(x)
        for j, kind in enumerate(cfg.pattern):
            x, _ = _mix_train(cfg, gparams[f"pos{j}"], x, kind, enc_out)
        return constrain_batch(x), None

    body = group_body
    if remat:
        body = jax.checkpoint(group_body, prevent_cse=False)
    if cfg.n_groups:
        x, _ = jax.lax.scan(body, x, params["groups"])
    for j, kind in enumerate(cfg.remainder):
        x, _ = _mix_train(cfg, params["rem"][f"pos{j}"], x, kind, enc_out)
    if "final_norm" in params:
        x = apply_norm(cfg, params["final_norm"], x)
    elif cfg.norm == "ln_nonparam":
        from .layers import ln_nonparam
        x = ln_nonparam(x)
    return x


def forward_prefill(cfg, params, tokens, extra: Optional[Dict] = None):
    """Full-sequence forward that also returns per-layer caches.

    Returns (hidden [B,S,d], caches) — caches is a dict:
      {"pos{j}": stacked-over-groups cache, "rem{j}": cache, "enc_out": ...}
    Attention caches are dense [n_groups, B, S, KH, hd] K/V (the serving
    engine paginates them into pages allocated by the block allocator).
    """
    x = embed_apply(params["embed"], tokens).astype(cfg.jdtype)
    if cfg.arch_kind == "vlm" and extra and "img_embeds" in extra:
        x = jnp.concatenate([extra["img_embeds"].astype(cfg.jdtype), x], axis=1)
    x = constrain_batch(x)
    enc_out = None
    if cfg.arch_kind == "encdec":
        enc_out = _encoder_apply(cfg, params,
                                 extra["enc_embeds"].astype(cfg.jdtype))
        enc_out = constrain_batch(enc_out)

    def group_body(x, gparams):
        x = constrain_batch(x)
        caches = {}
        for j, kind in enumerate(cfg.pattern):
            x, c = _mix_train(cfg, gparams[f"pos{j}"], x, kind, enc_out)
            caches[f"pos{j}"] = c
        return constrain_batch(x), caches

    caches = {}
    if cfg.n_groups:
        x, caches = jax.lax.scan(group_body, x, params["groups"])
    for j, kind in enumerate(cfg.remainder):
        x, c = _mix_train(cfg, params["rem"][f"pos{j}"], x, kind, enc_out)
        caches[f"rem{j}"] = jax.tree.map(lambda a: a[None], c)
    if enc_out is not None:
        caches["enc_out"] = enc_out
    if "final_norm" in params:
        x = apply_norm(cfg, params["final_norm"], x)
    elif cfg.norm == "ln_nonparam":
        from .layers import ln_nonparam
        x = ln_nonparam(x)
    return x, caches


# ============================================================== decode state

class DecodeState(NamedTuple):
    """All per-sequence serving state, [DP, B_local, ...] layouts.

    kv_pages:    dict pos -> (k, v) [n_stack, DP, pages_local, KH, psz, hd]
                 — head-major pages: the attention kernels read the
                 stack in place as [(n_stack*DP*pages_local)*KH, psz,
                 hd] tiles (DESIGN.md §3)
    rings:       dict pos -> (k, v) [n_stack, DP, Bl, W, KH, hd]
    rec:         dict pos -> pytree of recurrent states [n_stack, DP, Bl, ...]
    page_tables: int32 [DP, Bl, max_pages]   (shared by all paged layers)
    seq_lens:    int32 [DP, Bl]
    pool:        ClassedPool with leading-[DP] leaves per class — class
                 CLS_KV (always present) backs the paged KV with
                 per-slot private lanes of capacity 3*ell over a
                 per-shard shared stack; a two-class config adds the
                 fine CLS_STATE class accounting for bounded per-slot
                 state (ring windows, recurrent state, encoder KV) at
                 small-page granularity (ids shard-local AND
                 class-local; all mutation via classed_pool.*)
    enc_kv:      optional (k, v) [n_enc_stack?, ...] cross-attn KV (encdec)
    state_tables: optional int32 [DP, Bl, state_blocks_per_slot] — the
                 CLS_STATE block grants backing each slot's bounded
                 state (granted at admission, freed at release); None
                 in a single-class config
    expert_pages: optional [DP, NB2, d_model*d_ff] — CLS_EXPERT page
                 payloads (expert-paged MoE, DESIGN.md §15): each page
                 holds exactly one expert weight matrix, flat.  Written
                 only by the host-side expert loader; the jitted step
                 reads them via gathers (read-only shared pages).
    expert_tables: optional dict pos -> int32 [stack, DP, E, EXPERT_PPE]
                 — page ids of each MoE layer slot's experts (w_gate,
                 w_up, w_down), NULL for non-resident.  Mutated only by
                 the host-side ledger (load/evict); None when expert
                 paging is off
    """
    kv_pages: Dict[str, Tuple[jax.Array, jax.Array]]
    rings: Dict[str, Tuple[jax.Array, jax.Array]]
    rec: Dict[str, Any]
    page_tables: jax.Array
    seq_lens: jax.Array
    pool: classed_pool.ClassedPool
    enc_kv: Any
    state_tables: Any = None
    expert_pages: Any = None
    expert_tables: Any = None


#: pages per expert in the CLS_EXPERT class: one page per weight matrix
#: (w_gate, w_up, w_down), each exactly d_model*d_ff elements flat.
EXPERT_PPE = 3


def moe_positions(cfg) -> Tuple[list, list]:
    """(pattern MoE positions, remainder MoE positions) that carry an
    expert FFN — the layer slots the expert page tables index."""
    pat = [f"pos{j}" for j, k in enumerate(cfg.pattern)
           if is_moe_kind(k) and base_kind(k) != "ssd" and cfg.d_ff]
    rem = [f"rem{j}" for j, k in enumerate(cfg.remainder)
           if is_moe_kind(k) and base_kind(k) != "ssd" and cfg.d_ff]
    return pat, rem


def expert_layer_slots(cfg) -> int:
    """Total MoE layer slots = scanned groups x pattern MoE positions +
    remainder MoE positions (each slot owns E experts x EXPERT_PPE
    potential pages)."""
    pat, rem = moe_positions(cfg)
    return cfg.n_groups * len(pat) + len(rem)


def _positions(cfg) -> Dict[str, list]:
    """Map pattern position -> layer-state kind ('paged'|'ring'|'rec')."""
    kinds = {}
    for j, k in enumerate(cfg.pattern):
        bk = base_kind(k)
        if bk == "global":
            kinds[f"pos{j}"] = "paged"
        elif bk == "local":
            kinds[f"pos{j}"] = "ring"
        else:
            kinds[f"pos{j}"] = "rec"
    return kinds


def pool_ell(cfg, chunk: Optional[int] = None) -> int:
    """Lane batch size: ell >= the max pages one chunk can demand
    (ceil(chunk / page_size)), so the §4.2 never-dry invariant holds by
    construction — a slot's private lane always covers the next step's
    worst-case demand between rebalances."""
    chunk = chunk if chunk is not None else 2 * cfg.page_size
    return max(-(-int(chunk) // cfg.page_size), 2)


def state_page_tokens(cfg) -> int:
    """Granularity (token-capacity units) of the fine CLS_STATE class —
    a quarter KV page.  The class-boundary heuristic from the PAPERS.md
    reallocation analyses: small enough that bounded state (ring
    windows, recurrent blocks, encoder KV) stops rounding up to whole
    KV pages, large enough that the class's lane/table overhead stays
    negligible (DESIGN.md §14 routing table)."""
    return max(1, cfg.page_size // 4)


def state_blocks_per_slot(cfg, max_len: int) -> int:
    """CLS_STATE blocks one slot's bounded state occupies, at
    :func:`state_page_tokens` granularity.

    Rings charge their window per ring layer, recurrent layers one
    block each (fixed-size state), encoder KV its enc_len per decoder
    layer.  This is the accounting plane for state that is physically
    dense slot-indexed slabs: the grants are real allocator traffic
    (conservation-checked, §4.2-proven per class) so admission and
    occupancy meter bounded state at its own granularity instead of
    rounding up to KV pages — the §10 over-allocation the size-classed
    bench measures."""
    psz_s = state_page_tokens(cfg)
    blocks = 0
    kinds = _positions(cfg)
    W = min(cfg.window or max_len, max_len)
    for j, _ in enumerate(cfg.pattern):
        kind = kinds[f"pos{j}"]
        if kind == "ring":
            blocks += cfg.n_groups * -(-W // psz_s)
        elif kind == "rec":
            blocks += cfg.n_groups
    for k in cfg.remainder:
        bk = base_kind(k)
        if bk == "local":
            blocks += -(-W // psz_s)
        elif bk not in ("global",):
            blocks += 1
    if cfg.arch_kind == "encdec":
        stack = cfg.n_groups + len(cfg.remainder)
        blocks += stack * -(-cfg.enc_len // psz_s)
    return blocks


def pool_class_specs(cfg, b_local: int, max_len: int,
                     chunk: Optional[int] = None,
                     size_classes: int = 1,
                     expert_budget: Optional[int] = None
                     ) -> Tuple[ClassSpec, ...]:
    """The static class vector (DESIGN.md §14/§15), sized per class.

    Class 0 (CLS_KV) is the coarse paged-KV class: the pre-classed
    single-pool sizing verbatim — worst-case live pages for every local
    slot at max length PLUS fully-stocked lanes (3*ell per slot), the
    §4.2 slack.  With ``size_classes >= 2``, class 1 (CLS_STATE) is the
    fine bounded-state class with the same per-class slack rule at its
    own granularity and demand (``state_blocks_per_slot``).  With
    ``size_classes >= 3``, class 2 (CLS_EXPERT) is the read-only
    expert-weight class: ``expert_budget`` pages of ``d_model * d_ff``
    elements each (default: full residency — every expert of every MoE
    layer slot), plus the same 3*ell slack so the per-class §4.2
    argument holds verbatim.
    """
    psz = cfg.page_size
    max_pages = max(max_len // psz, 1)
    ell0 = pool_ell(cfg, chunk)
    specs = [ClassSpec(page_size=psz,
                       num_blocks=b_local * max_pages + 3 * ell0 * b_local,
                       num_lanes=b_local, ell=ell0)]
    if size_classes >= 2:
        sbs = state_blocks_per_slot(cfg, max_len)
        ell1 = 2       # in-step demand is frees only; keep the floor
        specs.append(ClassSpec(
            page_size=state_page_tokens(cfg),
            num_blocks=b_local * sbs + 3 * ell1 * b_local,
            num_lanes=b_local, ell=ell1))
    if size_classes >= 3:
        if expert_budget is None:
            expert_budget = (expert_layer_slots(cfg)
                             * cfg.moe.num_experts * EXPERT_PPE)
        ell2 = 2       # loads/evictions are host-paced, not in-step
        specs.append(ClassSpec(
            page_size=cfg.d_model * cfg.d_ff,
            num_blocks=int(expert_budget) + 3 * ell2 * b_local,
            num_lanes=b_local, ell=ell2))
    return tuple(specs)


def decode_state_defs(cfg, dp: int, b_local: int, max_len: int,
                      chunk: Optional[int] = None,
                      size_classes: int = 1,
                      expert_budget: Optional[int] = None):
    """ShapeDtypeStruct tree for the decode state (dry-run input).

    ``chunk`` is the serving engine's max tokens per step per sequence;
    it sizes the private-lane batch ``ell`` (see :func:`pool_ell`).
    ``size_classes`` sets the allocation-plane class vector
    (:func:`pool_class_specs`): 1 = the single coarse KV class
    (bit-identical to the pre-classed plane), 2 adds the fine
    bounded-state class and the ``state_tables`` register, 3 adds the
    read-only CLS_EXPERT class with its page payloads and per-MoE-layer
    expert tables (``expert_budget`` pages; DESIGN.md §15).
    """
    psz = cfg.page_size
    KH, hd = cfg.n_kv_heads, cfg.hd
    dt = cfg.jdtype
    ng = cfg.n_groups
    max_pages = max(max_len // psz, 1)
    specs = pool_class_specs(cfg, b_local, max_len, chunk, size_classes,
                             expert_budget)
    # per-shard KV page pool: enough for all local sequences at max
    # length PLUS fully-stocked lanes (3*ell per slot) — so rebalance
    # can keep every lane at >= ell free blocks even at peak occupancy
    pages_local = specs[CLS_KV].num_blocks
    kv_pages, rings, rec = {}, {}, {}

    def entry(pos, kind, stack):
        if kind == "paged":
            shp = (stack, dp, pages_local, KH, psz, hd)
            kv_pages[pos] = (jax.ShapeDtypeStruct(shp, dt),
                             jax.ShapeDtypeStruct(shp, dt))
        elif kind == "ring":
            W = min(cfg.window or max_len, max_len)
            shp = (stack, dp, b_local, W, KH, hd)
            rings[pos] = (jax.ShapeDtypeStruct(shp, dt),
                          jax.ShapeDtypeStruct(shp, dt))
        else:
            j = int(pos[3:])
            kind_name = (cfg.pattern + cfg.remainder)[j] if pos.startswith("pos") else None
            rec[pos] = _rec_state_defs(cfg, kind_name, stack, dp, b_local)

    for j, k in enumerate(cfg.pattern):
        entry(f"pos{j}", _positions(cfg)[f"pos{j}"], ng)
    for j, k in enumerate(cfg.remainder):
        pos = f"rem{j}"
        k = base_kind(k)
        if k == "global":
            shp = (1, dp, pages_local, KH, psz, hd)
            kv_pages[pos] = (jax.ShapeDtypeStruct(shp, dt),) * 2
        elif k == "local":
            W = min(cfg.window or max_len, max_len)
            shp = (1, dp, b_local, W, KH, hd)
            rings[pos] = (jax.ShapeDtypeStruct(shp, dt),) * 2
        else:
            rec[pos] = _rec_state_defs(cfg, k, 1, dp, b_local)

    enc_kv = None
    if cfg.arch_kind == "encdec":
        shp = (cfg.n_groups + len(cfg.remainder), dp, b_local,
               cfg.enc_len, cfg.n_kv_heads, cfg.hd)
        enc_kv = (jax.ShapeDtypeStruct(shp, dt), jax.ShapeDtypeStruct(shp, dt))

    def class_def(s: ClassSpec):
        return hier_pool.HierPool(
            shared=block_pool.BlockPool(
                free_ids=jax.ShapeDtypeStruct((dp, s.num_blocks), jnp.int32),
                top=jax.ShapeDtypeStruct((dp,), jnp.int32),
                refcount=jax.ShapeDtypeStruct((dp, s.num_blocks), jnp.int16)),
            private_ids=jax.ShapeDtypeStruct(
                (dp, s.num_lanes, 3 * s.ell), jnp.int32),
            private_top=jax.ShapeDtypeStruct((dp, s.num_lanes), jnp.int32))

    pool = classed_pool.ClassedPool(
        classes=tuple(class_def(s) for s in specs))
    state_tables = None
    if size_classes >= 2:
        sbs = max(state_blocks_per_slot(cfg, max_len), 1)
        state_tables = jax.ShapeDtypeStruct((dp, b_local, sbs), jnp.int32)
    expert_pages = expert_tables = None
    if size_classes >= 3:
        pe = cfg.d_model * cfg.d_ff
        E = cfg.moe.num_experts
        expert_pages = jax.ShapeDtypeStruct(
            (dp, specs[2].num_blocks, pe), dt)
        pat_moe, rem_moe = moe_positions(cfg)
        expert_tables = {}
        for pos in pat_moe:
            expert_tables[pos] = jax.ShapeDtypeStruct(
                (ng, dp, E, EXPERT_PPE), jnp.int32)
        for pos in rem_moe:
            expert_tables[pos] = jax.ShapeDtypeStruct(
                (1, dp, E, EXPERT_PPE), jnp.int32)

    return DecodeState(
        kv_pages=kv_pages, rings=rings, rec=rec,
        page_tables=jax.ShapeDtypeStruct((dp, b_local, max_pages), jnp.int32),
        seq_lens=jax.ShapeDtypeStruct((dp, b_local), jnp.int32),
        pool=pool,
        enc_kv=enc_kv,
        state_tables=state_tables,
        expert_pages=expert_pages,
        expert_tables=expert_tables,
    )


def _rec_state_defs(cfg, kind, stack, dp, b_local):
    if kind == "ssd":
        di = cfg.ssd_expand * cfg.d_model
        H = di // cfg.ssd_head_dim
        return {
            "h": jax.ShapeDtypeStruct(
                (stack, dp, b_local, H, cfg.ssd_head_dim, cfg.ssd_state),
                jnp.float32),
            "conv": jax.ShapeDtypeStruct(
                (stack, dp, b_local, 3, di + 2 * cfg.ssd_state), cfg.jdtype),
        }
    return {
        "h": jax.ShapeDtypeStruct(
            (stack, dp, b_local, cfg.d_model), jnp.float32),
        "conv": jax.ShapeDtypeStruct(
            (stack, dp, b_local, cfg.rglru_conv - 1, cfg.d_model), cfg.jdtype),
    }


# ======================================================= chunked decode path

@scoped("kv_write")
def _paged_write_chunk(k_pages, v_pages, layer, k_new, v_new, tables, base,
                       tok_valid):
    """Write a chunk's K/V into layer ``layer`` of the stacked pools.

    k_pages: [L, DP, P, KH, psz, hd]; k_new: [DP, Bl, T, KH, hd];
    tables: [DP, Bl, maxp]; base: [DP, Bl], the chunk's first position;
    tok_valid: [DP, Bl, T].  Whole pages are rewritten: the n <=
    ceil((T-1)/psz) + 1 pages each lane's chunk touches are gathered,
    the chunk's tokens merged into their slots, and the pages scattered
    back.  Layer, shard and page are the only scatter indices and the
    window is the whole [KH, psz, hd] page, contiguous in the head-major
    layout, so XLA updates the pool in place by page DMAs.  A scatter of
    one ``hd`` row per token and head is in place too, but the TPU runs
    its rows one by one (43 ms a 64-token step at olmo-1b widths); a
    slice among the indices (``at[l, d, pid, :, pip]``) makes XLA pick
    a psz-minor layout and copy the whole stack per layer.  A page with
    no valid token is dropped (out-of-range page index); pages a lane
    writes are its own (shared prefix pages are full, DESIGN.md §7)."""
    DP, Bl, T = k_new.shape[:3]
    P, _, psz = k_pages.shape[2:5]
    maxp = tables.shape[2]
    n = (T + psz - 2) // psz + 1
    slot = base[..., None] // psz + jnp.arange(n)                # [DP,Bl,n]
    pid = jnp.take_along_axis(tables, jnp.minimum(slot, maxp - 1), axis=2)
    # slot s of page k holds chunk token t = k*psz + s - base % psz;
    # t: [DP, Bl, n, psz]
    t = (jnp.arange(n)[:, None] * psz + jnp.arange(psz)
         - (base % psz)[..., None, None])
    tc = jnp.clip(t, 0, T - 1).reshape(DP, Bl, n * psz)
    valid = ((t >= 0) & (t < T) & jnp.take_along_axis(
        tok_valid, tc, axis=2).reshape(t.shape))
    valid = valid & ((pid >= 0) & (slot < maxp))[..., None]
    tgt = jnp.where(jnp.any(valid, axis=3), pid, P)              # P => drop
    d = jnp.arange(DP)[:, None, None]
    keep = valid[:, :, :, None, :, None]

    def write(pages, new):
        rows = jnp.take_along_axis(new, tc[..., None, None], axis=2)
        rows = rows.reshape(DP, Bl, n, psz, *new.shape[3:]).swapaxes(3, 4)
        old = pages[layer, d, jnp.minimum(tgt, P - 1)]  # [DP,Bl,n,KH,psz,hd]
        merged = jnp.where(keep, rows.astype(pages.dtype), old)
        return pages.at[layer, d, tgt].set(merged, mode="drop")

    return write(k_pages, k_new), write(v_pages, v_new)


def _paged_attn_chunk(q, k_pages, v_pages, layer, tables, base,
                      verify=False):
    """q: [DP, Bl, T, H, hd]; pages: the stacked [L, DP, P, KH, psz, hd];
    layer: the layer read; base: [DP, Bl].

    Reads the stack in place: merging its leading dims is a free
    reshape, and shard d's page ids of layer ``layer`` are offset by
    (layer*DP + d)*P, so one pallas_call / ref call covers all shards —
    no vmap over the kernel, no slice of the layer.  Dispatches the
    Pallas chunk kernel on TPU, jnp ref elsewhere.
    verify=True routes through the page-grouped verify-attention
    schedule (kernels/verify_attention) — bit-identical math, but each
    hot shared page is streamed from HBM once for all draft lanes
    reading it instead of once per lane.
    """
    DP, Bl, T, H, hd = q.shape
    L, _, P = k_pages.shape[:3]
    maxp = tables.shape[2]
    with scope("kv_layout"):
        off = ((layer * DP + jnp.arange(DP, dtype=jnp.int32)) * P)
        tg = jnp.where(tables >= 0, tables + off[:, None, None], -1)
        tg = tg.reshape(DP * Bl, maxp)
        kg = k_pages.reshape((L * DP * P,) + k_pages.shape[3:])
        vg = v_pages.reshape((L * DP * P,) + v_pages.shape[3:])
    op = verify_attention if verify else paged_attention_chunk
    o = op(q.reshape(DP * Bl, T, H, hd), kg, vg, tg, base.reshape(DP * Bl))
    return o.reshape(DP, Bl, T, H, hd)


@scoped("kv_write")
def _ring_write_chunk(k_ring, v_ring, k_new, v_new, positions, tok_valid,
                      lens):
    """Write a chunk into the rings.  positions/tok_valid: [DP, Bl, T].

    Only the last W valid tokens of a chunk can survive in a ring of
    size W; masking the rest out also removes duplicate-slot scatters
    when T > W."""
    DP, Bl, W = k_ring.shape[:3]
    T = k_new.shape[2]
    t = jnp.arange(T)[None, None, :]
    write = tok_valid & (t >= lens[..., None] - W)
    slot = jnp.where(write, positions % W, W)
    dp_i = jnp.arange(DP)[:, None, None]
    bl_i = jnp.arange(Bl)[None, :, None]
    k_ring = k_ring.at[dp_i, bl_i, slot].set(
        k_new.astype(k_ring.dtype), mode="drop")
    v_ring = v_ring.at[dp_i, bl_i, slot].set(
        v_new.astype(v_ring.dtype), mode="drop")
    return k_ring, v_ring


def _ring_attn_chunk(cfg, q, k_ring, v_ring, k_chunk, v_chunk, base, lens):
    """Chunked sliding-window attention over ring + in-chunk K/V.

    q: [DP, Bl, T, H, hd]; ring: [DP, Bl, W, KH, hd] holding the
    PRE-chunk content; k/v_chunk: [DP, Bl, T, KH, hd]; base/lens:
    [DP, Bl].  Query t (absolute position base + t) attends to ring
    tokens (absolute <= base - 1) and chunk tokens t' <= t, both
    windowed.  Attention runs before the ring is overwritten so early
    queries still see tokens that later chunk tokens will evict.
    """
    DP, Bl, T, H, hd = q.shape
    W = k_ring.shape[2]
    win = cfg.window or W
    r = jnp.arange(W)
    last = base - 1
    # absolute position currently stored in ring slot r (<= base - 1)
    abs_ring = r[None, None] + W * ((last[..., None] - r[None, None]) // W)
    t_idx = jnp.arange(T)
    qpos = base[..., None] + t_idx                               # [DP,Bl,T]
    valid_ring = ((abs_ring[:, :, None, :] >= 0) &
                  (abs_ring[:, :, None, :] > qpos[..., None] - win))
    tp = t_idx[None, None, None, :]
    tq = t_idx[None, None, :, None]
    valid_chunk = ((tp <= tq) & (tp < lens[..., None, None]) &
                   (tq - tp < win))
    valid = jnp.concatenate(
        [valid_ring, jnp.broadcast_to(valid_chunk, (DP, Bl, T, T))], axis=3)
    k = jnp.concatenate([k_ring, k_chunk.astype(k_ring.dtype)], axis=2)
    v = jnp.concatenate([v_ring, v_chunk.astype(v_ring.dtype)], axis=2)
    ke = attn._expand_kv(k.reshape(DP * Bl, W + T, -1, hd), H)
    ve = attn._expand_kv(v.reshape(DP * Bl, W + T, -1, hd), H)
    qf = q.reshape(DP * Bl, T, H, hd)
    s = jnp.einsum("bthd,bkhd->bhtk", qf, ke) / (hd ** 0.5)
    vm = valid.reshape(DP * Bl, 1, T, W + T)
    s = jnp.where(vm, s.astype(jnp.float32), attn.NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.any(vm, axis=-1, keepdims=True), p, 0.0)
    o = jnp.einsum("bhtk,bkhd->bthd", p.astype(q.dtype), ve)
    return o.reshape(DP, Bl, T, H, hd)


def _xattn_decode_chunk(cfg, lp, x, enc_kv_layer):
    """Cross-attention for a chunk of decode tokens.

    x: [DP, Bl, T, d]; enc_kv: [DP, Bl, L, KH, hd] (not causal)."""
    DP, Bl, T, d = x.shape
    h = apply_norm(cfg, lp["norm_x"], x)
    q = jnp.einsum("xbtd,dhk->xbthk", h, lp["xattn"]["wq"])
    k, v = enc_kv_layer
    ke = attn._expand_kv(k.reshape(DP * Bl, cfg.enc_len, -1, cfg.hd),
                         cfg.n_heads)
    ve = attn._expand_kv(v.reshape(DP * Bl, cfg.enc_len, -1, cfg.hd),
                         cfg.n_heads)
    qf = q.reshape(DP * Bl, T, cfg.n_heads, cfg.hd)
    s = jnp.einsum("bthd,bkhd->bhtk", qf, ke) / (cfg.hd ** 0.5)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    o = jnp.einsum("bhtk,bkhd->bthd", p.astype(x.dtype), ve)
    o = o.reshape(DP, Bl, T, cfg.n_heads, cfg.hd)
    return x + jnp.einsum("xbthk,hkd->xbtd", o, lp["xattn"]["wo"])


def _mix_decode_chunk(cfg, lp, x, kind, st_kind, layer_state, state,
                      positions, tok_valid, base, lens, enc_kv_layer=None,
                      verify=False, expert_buf=None, expert_mask=None,
                      layer=0):
    """One layer over a chunk of up to T tokens per sequence.

    x: [DP, Bl, T, d].  A paged layer's ``layer_state`` is the whole
    stacked (k, v) pool and ``layer`` its index there; every other
    layer's is its own slice.  Attention layers process the chunk in
    parallel (pages / ring written once, one chunk-attention call);
    recurrent layers scan the chunk sequentially with per-token state
    gating so ragged tails stay inert.  Returns (x, new_layer_state, moe_meta):
    ``moe_meta`` is None for non-MoE layers, else ``(dropped [DP],
    routed [DP, E])`` — capacity-dropped valid assignments per shard and
    valid kept assignments per expert (the §15 meters).

    ``expert_buf`` ([E, EXPERT_PPE, d*d_ff], shard-local, DP == 1) is
    the prefetched CLS_EXPERT page gather for this layer's experts; when
    given, the MoE FFN runs on weights reconstructed from it instead of
    resident ``lp["ffn"]`` matrices — the SAME compute path on the same
    values, so paged and resident serving are bit-identical.
    ``expert_mask`` (bool [DP, Bl, E]) is the admitted expert footprint.
    """
    DP, Bl, T, d = x.shape
    kind = base_kind(kind)
    h = apply_norm(cfg, lp["norm1"], x)
    if kind in ("global", "local"):
        with scope("attn"):
            hf = h.reshape(DP * Bl, T, d)
            pos_flat = positions.reshape(DP * Bl, T)
            q = jnp.einsum("bsd,dhk->bshk", hf, lp["attn"]["wq"])
            k = jnp.einsum("bsd,dhk->bshk", hf, lp["attn"]["wk"])
            v = jnp.einsum("bsd,dhk->bshk", hf, lp["attn"]["wv"])
            q = apply_rope(q, pos_flat, cfg.rope_theta)
            k = apply_rope(k, pos_flat, cfg.rope_theta)
            qd = q.reshape(DP, Bl, T, cfg.n_heads, cfg.hd)
            kd = k.reshape(DP, Bl, T, cfg.n_kv_heads, cfg.hd)
            vd = v.reshape(DP, Bl, T, cfg.n_kv_heads, cfg.hd)
            if st_kind == "paged":
                kp, vp = _paged_write_chunk(*layer_state, layer, kd, vd,
                                            state.page_tables, base,
                                            tok_valid)
                o = _paged_attn_chunk(qd, kp, vp, layer, state.page_tables,
                                      base, verify=verify)
                new_state = (kp, vp)
            else:
                kr, vr = layer_state
                o = _ring_attn_chunk(cfg, qd, kr, vr, kd, vd, base, lens)
                kr, vr = _ring_write_chunk(kr, vr, kd, vd, positions,
                                           tok_valid, lens)
                new_state = (kr, vr)
            x = x + jnp.einsum("xbthk,hkd->xbtd", o, lp["attn"]["wo"])
    else:  # ssd / rglru — sequential recurrence, scanned over the chunk
        def tok_body(st, inp):
            ht, valid_t = inp                      # [DP,Bl,d], [DP,Bl]
            if kind == "ssd":
                o, (hn, cn) = ssm_mod.ssd_block_apply(
                    cfg, lp["ssd"], ht.reshape(DP * Bl, 1, d),
                    h0=st["h"].reshape(DP * Bl, *st["h"].shape[2:]),
                    conv0=st["conv"].reshape(DP * Bl, *st["conv"].shape[2:]),
                    decode=True)
            else:
                o, (hn, cn) = rglru_mod.rglru_block_apply(
                    cfg, lp["rglru"], ht.reshape(DP * Bl, 1, d),
                    h0=st["h"].reshape(DP * Bl, d),
                    conv0=st["conv"].reshape(DP * Bl, *st["conv"].shape[2:]),
                    decode=True)
            new_st = {"h": hn.reshape(DP, Bl, *hn.shape[1:]),
                      "conv": cn.reshape(DP, Bl, *cn.shape[1:])}

            def g(nw, old):
                m = valid_t.reshape((DP, Bl) + (1,) * (nw.ndim - 2))
                return jnp.where(m, nw, old)

            new_st = jax.tree.map(g, new_st, st)
            return new_st, o[:, 0].reshape(DP, Bl, d)

        new_state, o_seq = jax.lax.scan(
            tok_body, layer_state,
            (h.transpose(2, 0, 1, 3), tok_valid.transpose(2, 0, 1)))
        x = x + o_seq.transpose(1, 2, 0, 3)

    if "xattn" in lp and enc_kv_layer is not None:
        x = _xattn_decode_chunk(cfg, lp, x, enc_kv_layer)

    moe_meta = None
    if "ffn" in lp:
        with scope("ffn"):
            h2 = apply_norm(cfg, lp["norm2"], x)
            h2f = h2.reshape(DP * Bl, T, d)
            if "router" in lp["ffn"]:
                E = cfg.moe.num_experts
                eff = lp["ffn"]
                if expert_buf is not None:
                    # paged experts: rebuild the stacked [E, ...] weight
                    # views from the gathered CLS_EXPERT pages and run the
                    # IDENTICAL dispatch path.  Non-resident experts gather
                    # page 0 (finite garbage) — the footprint mask keeps
                    # every valid token off them, and dropped/invalid rows
                    # contribute exactly 0 by the dispatch masking.
                    ff = cfg.d_ff
                    eff = {
                        "router": lp["ffn"]["router"],
                        "w_gate": expert_buf[:, 0].reshape(E, d, ff),
                        "w_up": expert_buf[:, 1].reshape(E, d, ff),
                        "w_down": expert_buf[:, 2].reshape(E, ff, d),
                    }
                mask = (None if expert_mask is None
                        else expert_mask.reshape(DP * Bl, E))
                f, dropped, routed = moe_mod.moe_apply(
                    cfg, eff, h2f, expert_mask=mask,
                    token_valid=tok_valid.reshape(DP * Bl, T), metered=True)
                moe_meta = (dropped.reshape(DP, Bl).sum(axis=1),
                            routed.reshape(DP, Bl, E).sum(axis=1))
            else:
                f = ffn_apply(cfg, lp["ffn"], h2f)
            x = x + f.reshape(DP, Bl, T, d)
    return x, new_state, moe_meta


def _gather_expert_pages(pages, tab):
    """Gather one MoE layer slot's expert weights off the CLS_EXPERT
    pages: pages [DP, NB2, pe] (DP == 1), tab int32 [DP, E, EXPERT_PPE]
    -> [E, EXPERT_PPE, pe].  NULL entries clamp to page 0 — finite
    garbage the footprint mask keeps every valid token away from."""
    p = pages[0]
    return p[jnp.clip(tab[0], 0, p.shape[0] - 1)]


def forward_decode_chunk(cfg, params, tokens, state: DecodeState, lens,
                         active=None, verify=False, expert_mask=None):
    """Chunked decode/prefill: up to T tokens per sequence per call.

    tokens: int32 [DP, Bl, T]; lens: int32 [DP, Bl] — valid tokens per
    sequence this call (ragged tails are inert: not written to any
    cache, recurrent state gated per token).  Returns (hidden
    [DP, Bl, T, d], new DecodeState, fwd_meta) with seq_lens advanced
    by lens; ``fwd_meta`` is a dict of int32[DP] MoE meters
    (``moe_dropped``, ``expert_hit_pages``, ``expert_miss_pages``,
    ``expert_prefetch_pages``) — all zeros for non-MoE configs.

    ``expert_mask`` (bool [DP, Bl, E], optional) restricts each slot's
    routing to its admitted expert footprint (applied at every MoE
    router — paged OR resident, so the two modes stay token-identical).
    When ``state.expert_tables`` is set (expert-paged serving), each
    scan iteration g consumes the expert pages gathered during
    iteration g-1 and issues the gather for group g+1's tables — the
    prefetch has no data dependence on group g's FFN, so XLA overlaps
    the page DMA with compute; routing for layer L+1 never waits on its
    weight gather (DESIGN.md §15 prefetch window).

    Pages for the WHOLE chunk (up to ceil(T/psz) per sequence) come
    from each slot's private lane in one
    :func:`hier_pool.alloc_n_or_shared` call — the paper's
    batch-granularity transfer absorbing multi-page demand per step in
    O(Bl * T) lane-local work, independent of the pool size (the §4.2
    sizing rule ``ell >= ceil(T/psz)`` keeps the lanes never-dry
    between rebalances, so the shared-pool fallback is dead code on
    the serving path; a caller looping this step raw, with no
    rebalance, degrades to the shared pool instead of writing through
    NULL page ids).  T == 1 with lens == active is steady-state
    single-token decode — a width-1 lane, the serving engine's decode
    path.
    """
    DP, Bl, T = tokens.shape
    if active is None:
        active = jnp.ones((DP, Bl), bool)
    lens = jnp.where(active, jnp.clip(lens.astype(jnp.int32), 0, T), 0)
    base = state.seq_lens
    x = constrain_batch(embed_apply(params["embed"], tokens).astype(cfg.jdtype))

    # --- page allocation for the whole chunk (once, all paged layers)
    if state.kv_pages:
        # all-or-nothing per sequence (append_chunk's contract): a chunk
        # that would overflow the page table, or whose pages the pool
        # denies, appends NOTHING — without the gate the page-index
        # clamp below would overwrite live KV while seq_lens advanced
        with scope("alloc"):
            psz = cfg.page_size
            maxp = state.page_tables.shape[2]
            kmax = -(-T // psz)
            lens, pages_before, counts = block_pool.chunk_page_plan(
                base, lens, psz, maxp)
            pool, got = classed_pool.alloc_n_or_shared_dp(
                state.pool, CLS_KV, counts, kmax)
            lens = jnp.where(block_pool.granted_mask(got, counts), lens, 0)
            dp_i = jnp.arange(DP)[:, None, None]
            bl_i = jnp.arange(Bl)[None, :, None]
            kk = jnp.arange(kmax)[None, None, :]
            slot = pages_before[..., None] + kk
            new_page = (kk < counts[..., None]) & (got >= 0)
            slot = jnp.where(new_page, slot, maxp)       # out-of-range => drop
            new_tables = state.page_tables.at[dp_i, bl_i, slot].set(
                got, mode="drop")
            state = state._replace(page_tables=new_tables, pool=pool)

    positions = base[..., None] + jnp.arange(T, dtype=jnp.int32)[None, None]
    tok_valid = jnp.arange(T)[None, None, :] < lens[..., None]

    st_kinds = _positions(cfg)
    has_x = cfg.arch_kind == "encdec"

    paged_moe = bool(state.expert_tables)
    if paged_moe:
        # the paged FFN squeezes the shard axis to rebuild [E, ...]
        # weights; under shard_map (or a dp=1 engine) DP is always 1
        assert DP == 1, "expert paging requires shard-local DP == 1"
    meters = {k: jnp.zeros((DP,), jnp.int32)
              for k in ("moe_dropped", "expert_hit_pages",
                        "expert_miss_pages", "expert_prefetch_pages")}

    def absorb(meters, meta, tab):
        """Fold one MoE layer's (dropped, routed) into the step meters;
        ``tab`` (int32 [DP, E, EXPERT_PPE] or None) supplies residency:
        an expert is resident iff all its pages are mapped."""
        if meta is None:
            return meters
        dropped, routed = meta
        meters = dict(meters)
        meters["moe_dropped"] = meters["moe_dropped"] + dropped
        if tab is not None:
            res = (tab >= 0).all(axis=-1)                  # [DP, E]
            touched = routed > 0
            meters["expert_hit_pages"] = (
                meters["expert_hit_pages"] + EXPERT_PPE * jnp.sum(
                    touched & res, axis=-1, dtype=jnp.int32))
            meters["expert_miss_pages"] = (
                meters["expert_miss_pages"] + EXPERT_PPE * jnp.sum(
                    touched & ~res, axis=-1, dtype=jnp.int32))
        return meters

    pat_moe, _rem_moe = moe_positions(cfg)
    etab_pat = ({pos: state.expert_tables[pos] for pos in pat_moe}
                if paged_moe else {})
    # next-group tables: group g prefetches g+1's experts (wraps to 0)
    etab_next = jax.tree.map(lambda a: jnp.roll(a, -1, axis=0), etab_pat)
    ebuf0 = {pos: _gather_expert_pages(state.expert_pages, tab[0])
             for pos, tab in etab_pat.items()}

    # the stacked paged pools ride the scan's carry, not its xs/ys: each
    # layer writes and reads its pages in place, with no per-layer slice,
    # write-back or copy of the stack (DESIGN.md §3)
    def group_body(carry, xs):
        x, ebuf, meters, kv = carry
        g, gparams, gstate, enc_kv_g, etab_g, etab_n = xs
        kv, new_gstate = dict(kv), {}
        for j, kind in enumerate(cfg.pattern):
            pos = f"pos{j}"
            paged = pos in kv
            x, ns, meta = _mix_decode_chunk(
                cfg, gparams[pos], x, kind, st_kinds[pos],
                kv[pos] if paged else gstate[pos],
                state, positions, tok_valid, base, lens,
                enc_kv_g if has_x else None, verify=verify,
                expert_buf=ebuf.get(pos), expert_mask=expert_mask, layer=g)
            if paged:
                kv[pos] = ns
            else:
                new_gstate[pos] = ns
            meters = absorb(meters, meta, etab_g.get(pos))
        # prefetch the NEXT group's expert pages: independent of this
        # group's FFN, so the gather DMA overlaps the compute above
        new_ebuf = {}
        for pos, tab_n in etab_n.items():
            new_ebuf[pos] = _gather_expert_pages(state.expert_pages, tab_n)
            meters = dict(meters)
            meters["expert_prefetch_pages"] = (
                meters["expert_prefetch_pages"] + jnp.sum(
                    tab_n >= 0, axis=(1, 2), dtype=jnp.int32))
        return (x, new_ebuf, meters, kv), new_gstate

    kv_g = {pos: kv for pos, kv in state.kv_pages.items()
            if pos.startswith("pos")}
    if cfg.n_groups:
        gstates = {}
        for pos, kv in state.rings.items():
            if pos.startswith("pos"):
                gstates[pos] = kv
        for pos, rc in state.rec.items():
            if pos.startswith("pos"):
                gstates[pos] = rc
        if has_x and state.enc_kv is not None:
            assert len(cfg.pattern) == 1, "encdec requires pattern length 1"
            enc_scan = (state.enc_kv[0][:cfg.n_groups],
                        state.enc_kv[1][:cfg.n_groups])
        else:
            enc_scan = (jnp.zeros((cfg.n_groups,)),) * 2  # placeholder
        # ``layers``: the scan's own per-layer slicing of the stacked
        # weights and xs states and their write-back carry no inner scope
        with scope("layers"):
            (x, _, meters, kv_g), new_gstates = jax.lax.scan(
                group_body, (x, ebuf0, meters, kv_g),
                (jnp.arange(cfg.n_groups, dtype=jnp.int32),
                 params["groups"], gstates, enc_scan, etab_pat, etab_next))
    else:
        new_gstates = {}

    new_rem_states = {}
    for j, kind in enumerate(cfg.remainder):
        pos = f"rem{j}"
        bk = base_kind(kind)
        st_kind = ("paged" if bk == "global"
                   else "ring" if bk == "local" else "rec")
        paged = pos in state.kv_pages
        if paged:       # a stack of 1, read and written in place at 0
            ls0 = state.kv_pages[pos]
        else:
            ls = state.rings.get(pos) or state.rec.get(pos)
            with scope("layers"):
                ls0 = jax.tree.map(lambda a: a[0], ls)
        lp = params["rem"][f"pos{j}"]
        enc_l = None
        if has_x and state.enc_kv is not None:
            idx = cfg.n_groups * len(cfg.pattern) + j
            enc_l = (state.enc_kv[0][idx], state.enc_kv[1][idx])
        tab_r = ebuf_r = None
        if paged_moe and pos in state.expert_tables:
            tab_r = state.expert_tables[pos][0]
            ebuf_r = _gather_expert_pages(state.expert_pages, tab_r)
        x, ns, meta = _mix_decode_chunk(cfg, lp, x, kind, st_kind, ls0,
                                        state, positions, tok_valid, base,
                                        lens, enc_l, verify=verify,
                                        expert_buf=ebuf_r,
                                        expert_mask=expert_mask)
        if paged:
            new_rem_states[pos] = ns
        else:
            with scope("layers"):
                new_rem_states[pos] = jax.tree.map(lambda a: a[None], ns)
        meters = absorb(meters, meta, tab_r)

    kv_pages, rings, rec = {}, {}, {}
    for pos in state.kv_pages:
        src = kv_g if pos.startswith("pos") else new_rem_states
        kv_pages[pos] = src[pos]
    for pos in state.rings:
        src = new_gstates if pos.startswith("pos") else new_rem_states
        rings[pos] = src[pos]
    for pos in state.rec:
        src = new_gstates if pos.startswith("pos") else new_rem_states
        rec[pos] = src[pos]
    # rec states were gated per token inside the chunk scan; no extra
    # active-gating needed here (lens == 0 leaves every leaf untouched).

    state = DecodeState(
        kv_pages=kv_pages, rings=rings, rec=rec,
        page_tables=state.page_tables,
        seq_lens=base + lens,
        pool=state.pool,
        enc_kv=state.enc_kv,
        state_tables=state.state_tables,
        expert_pages=state.expert_pages,
        expert_tables=state.expert_tables)

    if "final_norm" in params:
        x = apply_norm(cfg, params["final_norm"], x)
    elif cfg.norm == "ln_nonparam":
        from .layers import ln_nonparam
        x = ln_nonparam(x)
    return x, state, meters
