"""Step-function builders + input specs for every (arch x shape) cell.

Used by the dry-run (ShapeDtypeStruct inputs, .lower().compile()), the
trainer, and the serving engine.  All specs are mesh-aware:

* train/prefill: tokens [B, S] sharded over the batch axes;
* decode: [DP, B_local] layout with DP = min(#batch-shards, B); paged KV
  pages live in a per-DP-shard two-level HierPool with per-slot private
  lanes (see DESIGN.md §7 / transformer.py).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import models
from ..configs.base import ModelConfig, ShapeConfig
from ..models import transformer as tfm
from ..optim import adamw
from ..parallel import partition


# ----------------------------------------------------------------- helpers

def _ns(mesh, spec):
    return NamedSharding(mesh, spec)


def _maybe(mesh, dim, axis):
    """Shard dim over axis only if divisible (see partition.py)."""
    return axis if dim % partition._axis_size(mesh, axis) == 0 else None


def decode_layout(shape: ShapeConfig, mesh: Mesh) -> Tuple[int, int]:
    dp = min(partition.dp_size(mesh), shape.global_batch)
    return dp, shape.global_batch // dp


# ------------------------------------------------------------- input specs

def batch_specs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh):
    """(ShapeDtypeStruct tree, sharding tree) for the data batch."""
    ba = partition.batch_axes(mesh)
    ba = ba if len(ba) > 1 else ba[0]
    B, S = shape.global_batch, shape.seq_len
    if shape.mode in ("train", "prefill"):
        specs = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        shard = {"tokens": _ns(mesh, P(ba, None))}
        if shape.mode == "train":
            specs["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
            shard["labels"] = _ns(mesh, P(ba, None))
        if cfg.arch_kind == "vlm":
            specs["img_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.img_tokens, cfg.d_model), cfg.jdtype)
            shard["img_embeds"] = _ns(mesh, P(ba, None, None))
        if cfg.arch_kind == "encdec":
            specs["enc_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.enc_len, cfg.d_model), cfg.jdtype)
            shard["enc_embeds"] = _ns(mesh, P(ba, None, None))
        return specs, shard
    # decode
    dp, bl = decode_layout(shape, mesh)
    specs = jax.ShapeDtypeStruct((dp, bl), jnp.int32)
    shard = _ns(mesh, P(ba if dp > 1 else None, None))
    return specs, shard


def decode_state_shardings(cfg: ModelConfig, state_defs: tfm.DecodeState,
                           mesh: Mesh) -> tfm.DecodeState:
    ba = partition.batch_axes(mesh)
    ba = ba if len(ba) > 1 else ba[0]
    dp = state_defs.seq_lens.shape[0]
    dpa = ba if dp > 1 else None
    KH = cfg.n_kv_heads
    kh_ax = _maybe(mesh, KH, "model")
    # GQA with KH < model-axis: instead of replicating the KV pool
    # model-axis-wide (16x memory), shard the head_dim (128 % 16 == 0
    # for every assigned config).  The QK^T contraction over hd becomes
    # a partial sum + a tiny [B,H,L] all-reduce.  §Perf B2.
    hd_ax = None
    if kh_ax is None:
        hd_ax = _maybe(mesh, cfg.hd, "model")

    def kv_spec(sds, kh=-2):
        # rings, enc_kv [stack, DP, Bl, W, KH, hd]; pages head-major
        # [stack, DP, pages, KH, psz, hd] (kh=3)
        nd = len(sds.shape)
        parts = [None] * nd
        parts[1] = dpa
        parts[kh] = kh_ax
        parts[-1] = hd_ax
        return _ns(mesh, P(*parts))

    def rec_spec(sds):
        # shard the widest trailing dim over model if divisible
        parts = [None] * len(sds.shape)
        parts[1] = dpa
        # heads dim for ssd h [stack, DP, Bl, H, P, N]; channels for conv
        if len(sds.shape) >= 4:
            cand = 3
            parts[cand] = _maybe(mesh, sds.shape[cand], "model")
        return _ns(mesh, P(*parts))

    kv_pages = jax.tree.map(lambda a: kv_spec(a, kh=3), state_defs.kv_pages)
    rings = jax.tree.map(kv_spec, state_defs.rings)
    rec = jax.tree.map(rec_spec, state_defs.rec)
    enc_kv = None
    if state_defs.enc_kv is not None:
        enc_kv = jax.tree.map(kv_spec, state_defs.enc_kv)
    # every pool leaf (any class, any depth) carries DP at axis 0
    def pool_spec(sds):
        return _ns(mesh, P(*([dpa] + [None] * (len(sds.shape) - 1))))

    return tfm.DecodeState(
        kv_pages=kv_pages, rings=rings, rec=rec,
        page_tables=_ns(mesh, P(dpa, None, None)),
        seq_lens=_ns(mesh, P(dpa, None)),
        pool=jax.tree.map(pool_spec, state_defs.pool),
        enc_kv=enc_kv,
        state_tables=(None if state_defs.state_tables is None
                      else _ns(mesh, P(dpa, None, None))),
        expert_pages=(None if state_defs.expert_pages is None
                      else _ns(mesh, P(dpa, None, None))),
        expert_tables=(None if state_defs.expert_tables is None
                       else jax.tree.map(
                           lambda s: _ns(mesh, P(None, dpa, None, None)),
                           state_defs.expert_tables)))


# --------------------------------------------- serving dp-mesh partitioning
#
# The serving engine's allocation plane runs over the one-axis ("dp",)
# mesh of launch.mesh.make_dp_mesh: every DecodeState leaf is sharded on
# its DP axis so each device owns exactly its shard's HierPool (shared
# stack, refcounts, lanes), page tables, pin table, and KV pages, and
# the engine's jitted steps are shard_mapped over these specs
# (DESIGN.md §9).  Leaf layouts: kv_pages/rings/rec/enc_kv carry DP at
# axis 1 ([stack, DP, ...]); page_tables/seq_lens/pool leaves and the
# per-slot serving registers carry it at axis 0.  The §13 telemetry
# counter block widens the packed status array ([T+3+N_CTR, DP, Bl],
# DP at axis 1) — the extra rows ride the *existing* status out-spec
# and all_gather, so enabling telemetry changes no sharding and adds
# no collective.

def serve_register_pspec() -> P:
    """[DP, Bl(, ...)] per-slot register / mask / pin-table spec."""
    return P("dp")


def serve_state_pspecs(state: tfm.DecodeState) -> tfm.DecodeState:
    """PartitionSpec tree (axis name "dp") for a serving DecodeState."""
    ax1 = lambda tree: jax.tree.map(lambda _: P(None, "dp"), tree)
    return tfm.DecodeState(
        kv_pages=ax1(state.kv_pages),
        rings=ax1(state.rings),
        rec=ax1(state.rec),
        page_tables=P("dp"),
        seq_lens=P("dp"),
        pool=jax.tree.map(lambda _: P("dp"), state.pool),
        enc_kv=None if state.enc_kv is None else ax1(state.enc_kv),
        state_tables=None if state.state_tables is None else P("dp"),
        expert_pages=None if state.expert_pages is None else P("dp"),
        expert_tables=(None if state.expert_tables is None
                       else ax1(state.expert_tables)))


def serve_shardings(mesh: Mesh, pspecs):
    """NamedSharding tree for ``jax.device_put`` of serving state."""
    return jax.tree.map(lambda s: _ns(mesh, s), pspecs)


# ------------------------------------------------------------ step builders

def build_train_step(cfg: ModelConfig, opt_cfg: Optional[adamw.AdamWConfig] = None):
    opt_cfg = opt_cfg or adamw.AdamWConfig()

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: models.loss_fn(cfg, p, batch))(params)
        new_params, new_opt, metrics = adamw.apply(
            opt_cfg, opt_state, grads, params)
        metrics["loss"] = loss
        return new_params, new_opt, metrics

    return train_step


def build_prefill_step(cfg: ModelConfig):
    def prefill_step(params, batch):
        return models.prefill(cfg, params, batch)
    return prefill_step


def build_serve_step(cfg: ModelConfig):
    """Dry-run / roofline decode cell: one width-1 token lane per slot
    (``models.decode_step`` wraps ``forward_decode_chunk`` at T=1 —
    the only decode entry point since the single-token path was
    deleted; DESIGN.md §10)."""
    def serve_step(params, tokens, state):
        return models.decode_step(cfg, params, tokens, state)
    return serve_step


# ---------------------------------------------------------------- assembly

def cell_specs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
               rules: Optional[str] = None):
    """Everything needed to jit-lower one (arch x shape) cell.

    Returns dict with: fn, args (ShapeDtypeStructs), in_shardings,
    donate_argnums.
    """
    if rules is None:
        if shape.mode == "train":
            rules = "fsdp"
        elif cfg.moe is not None:
            rules = "ep_serve"   # §Perf B1: don't replicate experts
        else:
            rules = "tp"
    defs = models.param_defs(cfg)
    pshapes = models.param_shapes(cfg)
    pshard = partition.param_shardings(defs, mesh, rules)

    if shape.mode == "train":
        bspecs, bshard = batch_specs(cfg, shape, mesh)
        opt_shapes = _opt_shapes(pshapes)
        opt_shard = _opt_shardings(pshard, mesh)
        fn = build_train_step(cfg)
        return dict(fn=fn, args=(pshapes, opt_shapes, bspecs),
                    in_shardings=(pshard, opt_shard, bshard),
                    donate_argnums=(0, 1))
    if shape.mode == "prefill":
        bspecs, bshard = batch_specs(cfg, shape, mesh)
        fn = build_prefill_step(cfg)
        return dict(fn=fn, args=(pshapes, bspecs),
                    in_shardings=(pshard, bshard), donate_argnums=())
    # decode
    dp, bl = decode_layout(shape, mesh)
    sdefs = tfm.decode_state_defs(cfg, dp, bl, max_len=shape.seq_len)
    sshard = decode_state_shardings(cfg, sdefs, mesh)
    tspec, tshard = batch_specs(cfg, shape, mesh)
    fn = build_serve_step(cfg)
    return dict(fn=fn, args=(pshapes, tspec, sdefs),
                in_shardings=(pshard, tshard, sshard), donate_argnums=(2,))


def _opt_shapes(pshapes):
    f32 = lambda t: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), t)
    return adamw.AdamWState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        mu=f32(pshapes), nu=f32(pshapes), master=f32(pshapes))


def _opt_shardings(pshard, mesh):
    return adamw.AdamWState(
        step=_ns(mesh, P()),
        mu=pshard, nu=pshard, master=pshard)
