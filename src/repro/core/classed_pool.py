"""Size-classed allocation plane: one allocator, many fixed sizes.

The paper's O(1) allocate/free argument is *per fixed block size*, so
it generalizes verbatim to a small static vector of size classes: each
class is an independent :class:`~repro.core.hier_pool.HierPool`
(per-class private lanes over a per-class shared stack, per-class
drain/refill rebalance), and the §4.2 never-dry invariant is proven
independently per class — the classes never exchange blocks, so no
cross-class interaction can invalidate a class's slack argument
(DESIGN.md §14).  This is the bucketed ``pool_allocator`` shape
(SNIPPETS.md Snippet 1), with class boundaries chosen per the
reallocation analyses in PAPERS.md (Farach-Colton et al. 2405.12152,
Jin 2602.15417): a coarse class for paged KV (large pages amortize
page-table walks) and a fine class for small bounded state (ring
windows, recurrent state, encoder KV, draft-tail accounting) where a
whole KV-sized page would be mostly over-allocation.

Every op takes the class index ``cls`` as a *static* Python int — the
class vector is fixed at trace time, so a class-indexed call lowers to
exactly the single-class HLO on that class's leaves (single-class
configs are bit-identical to the pre-classed plane by construction).
``rebalance_*`` runs over ALL classes in one call so the jitted serve
step keeps its one-rebalance-per-step shape; passing ``cls`` rebalances
one class only (the torn per-class crash windows the chaos plane
injects).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..serving.trace import scoped
from . import hier_pool
from .hier_pool import HierPool


#: class index of the coarse paged-KV class — always present, always 0.
CLS_KV = 0
#: class index of the fine bounded-state class in a two-class config.
CLS_STATE = 1
#: class index of the read-only shared expert-weight class in a
#: three-class (expert-paged MoE) config.  Expert pages are never
#: written after load: residency is managed host-side through the same
#: addref/free_shared protocol pins use, with refcount = one ledger ref
#: + one ref per active batch routed through the expert (DESIGN.md §15).
CLS_EXPERT = 2


class ClassSpec(NamedTuple):
    """Static description of one size class."""
    page_size: int       # granularity, in token-capacity units
    num_blocks: int      # per-shard blocks in this class
    num_lanes: int       # private lanes (serving slots)
    ell: int             # lane batch size (lane capacity = 3*ell)


class ClassedPool(NamedTuple):
    """A static tuple of independent per-class HierPools (a pytree:
    tuples of NamedTuples of arrays — shard_map/vmap/jit transparent)."""
    classes: Tuple[HierPool, ...]


def n_classes(pool: ClassedPool) -> int:
    return len(pool.classes)


def cls_pool(pool: ClassedPool, cls: int) -> HierPool:
    """The class's underlying HierPool (read-only view)."""
    return pool.classes[cls]


def _put(pool: ClassedPool, cls: int, hp: HierPool) -> ClassedPool:
    cs = list(pool.classes)
    cs[cls] = hp
    return ClassedPool(classes=tuple(cs))


def validate_specs(specs: Sequence[ClassSpec],
                   max_live: Sequence[int], *,
                   degraded_ok: bool = False) -> Tuple[bool, ...]:
    """Plan-time §4.2 validation, per class (hier_pool.validate_plan).

    ``max_live[c]`` is class c's worst-case simultaneously-live blocks
    (the admission budget).  Raises ``ValueError`` naming the failing
    class unless ``degraded_ok``; returns the per-class fully-
    provisioned flags."""
    assert len(specs) == len(max_live)
    return tuple(
        hier_pool.validate_plan(
            s.num_blocks, s.num_lanes, s.ell, int(max_live[c]),
            degraded_ok=degraded_ok,
            what=f"class {c} (page_size={s.page_size})")
        for c, s in enumerate(specs))


def create(specs: Sequence[ClassSpec]) -> ClassedPool:
    """One single-shard HierPool per class."""
    return ClassedPool(classes=tuple(
        hier_pool.create(s.num_blocks, s.num_lanes, s.ell)
        for s in specs))


def create_dp(dp: int, specs: Sequence[ClassSpec]) -> ClassedPool:
    """One identical per-class pool vector per DP shard."""
    return ClassedPool(classes=tuple(
        hier_pool.create_dp(dp, s.num_blocks, s.num_lanes, s.ell)
        for s in specs))


# --------------------------------------------------- class-indexed ops
#
# Thin static-dispatch wrappers: extract class ``cls``, run the
# single-class op, put the result back.  Only the touched class's
# leaves appear in the lowered HLO.  Every device op here runs under
# the ``alloc`` device scope (serving/trace.py), so a profile gives the
# allocator's own device time wherever it is called from.

@scoped("alloc")
def alloc_n_dp(pool: ClassedPool, cls: int, counts: jax.Array,
               max_per_lane: int) -> Tuple[ClassedPool, jax.Array]:
    hp, ids = hier_pool.alloc_n_dp(pool.classes[cls], counts, max_per_lane)
    return _put(pool, cls, hp), ids


@scoped("alloc")
def alloc_n_or_shared_dp(pool: ClassedPool, cls: int, counts: jax.Array,
                         max_per_lane: int
                         ) -> Tuple[ClassedPool, jax.Array]:
    hp, ids = hier_pool.alloc_n_or_shared_dp(
        pool.classes[cls], counts, max_per_lane)
    return _put(pool, cls, hp), ids


@scoped("alloc")
def alloc_from_shared_dp(pool: ClassedPool, cls: int, counts: jax.Array,
                         max_per_lane: int
                         ) -> Tuple[ClassedPool, jax.Array]:
    hp, ids = hier_pool.alloc_from_shared_dp(
        pool.classes[cls], counts, max_per_lane)
    return _put(pool, cls, hp), ids


@scoped("alloc")
def free_n_dp(pool: ClassedPool, cls: int, ids: jax.Array) -> ClassedPool:
    return _put(pool, cls, hier_pool.free_n_dp(pool.classes[cls], ids))


@scoped("alloc")
def free_n_metered_dp(pool: ClassedPool, cls: int, ids: jax.Array
                      ) -> Tuple[ClassedPool, jax.Array]:
    hp, spilled = hier_pool.free_n_metered_dp(pool.classes[cls], ids)
    return _put(pool, cls, hp), spilled


@scoped("alloc")
def free_shared_dp(pool: ClassedPool, cls: int,
                   ids: jax.Array) -> ClassedPool:
    return _put(pool, cls, hier_pool.free_shared_dp(pool.classes[cls], ids))


@scoped("alloc")
def addref_dp(pool: ClassedPool, cls: int, ids: jax.Array) -> ClassedPool:
    return _put(pool, cls, hier_pool.addref_dp(pool.classes[cls], ids))


@scoped("alloc")
def rebalance_dp(pool: ClassedPool,
                 cls: Optional[int] = None) -> ClassedPool:
    """Deamortized rebalance — all classes (default) in one call, so
    the serve step keeps one fused rebalance per step; ``cls`` limits
    to one class (torn per-class windows in chaos tests)."""
    if cls is not None:
        return _put(pool, cls, hier_pool.rebalance_dp(pool.classes[cls]))
    return ClassedPool(classes=tuple(
        hier_pool.rebalance_dp(hp) for hp in pool.classes))


@scoped("alloc")
def rebalance_drain_dp(pool: ClassedPool,
                       cls: Optional[int] = None) -> ClassedPool:
    if cls is not None:
        return _put(pool, cls,
                    hier_pool.rebalance_drain_dp(pool.classes[cls]))
    return ClassedPool(classes=tuple(
        hier_pool.rebalance_drain_dp(hp) for hp in pool.classes))


@scoped("alloc")
def rebalance_refill_dp(pool: ClassedPool,
                        cls: Optional[int] = None) -> ClassedPool:
    if cls is not None:
        return _put(pool, cls,
                    hier_pool.rebalance_refill_dp(pool.classes[cls]))
    return ClassedPool(classes=tuple(
        hier_pool.rebalance_refill_dp(hp) for hp in pool.classes))


# ------------------------------------------------------------- queries

@scoped("alloc")
def free_per_shard(pool: ClassedPool, cls: int) -> jax.Array:
    return hier_pool.free_per_shard(pool.classes[cls])


@scoped("alloc")
def live_per_shard(pool: ClassedPool, cls: int) -> jax.Array:
    return hier_pool.live_per_shard(pool.classes[cls])


def lane_ell(pool: ClassedPool, cls: int) -> int:
    return hier_pool.lane_ell(pool.classes[cls])


def pages_local(pool: ClassedPool, cls: int) -> int:
    """Per-shard block capacity of class ``cls`` (static)."""
    return pool.classes[cls].shared.free_ids.shape[-1]


@scoped("alloc")
def total_free(pool: ClassedPool) -> jax.Array:
    """Free blocks summed over ALL classes (and shards)."""
    return sum(hier_pool.total_free(hp) for hp in pool.classes)


@scoped("alloc")
def num_live(pool: ClassedPool) -> jax.Array:
    """Live blocks summed over ALL classes (and shards)."""
    return sum(hier_pool.num_live(hp) for hp in pool.classes)


# ------------------------------------------------------ crash recovery

def audit_and_reconcile(pool: ClassedPool, keep_tables=None,
                        pin_tables=None) -> Tuple[ClassedPool, dict]:
    """Per-class :func:`hier_pool.audit_and_reconcile`, merged report.

    ``keep_tables`` / ``pin_tables`` are per-class sequences (or None
    for none anywhere); entry c holds class c's keeping rows (None
    allowed per class — e.g. pins exist only in the KV class).  The
    merged report carries per-class sub-reports under ``"classes"``
    plus the same top-level keys the single-pool form exposes
    (conservation and never-dry are ANDed over classes — the §4.2
    argument is per class, so recovery must prove it per class).
    """
    C = len(pool.classes)

    def per(tabs, c):
        return None if tabs is None else tabs[c]

    new_classes, reports = [], []
    for c in range(C):
        hp, rep = hier_pool.audit_and_reconcile(
            pool.classes[c], keep_tables=per(keep_tables, c),
            pin_tables=per(pin_tables, c))
        new_classes.append(hp)
        reports.append(rep)
    merged = {
        "classes": reports,
        "reclaimed": sum(r["reclaimed"] for r in reports),
        "resurrected": sum(r["resurrected"] for r in reports),
        "clamped": sum(r["clamped"] for r in reports),
        "never_dry": all(r["never_dry"] for r in reports),
        "conserved": all(r["conserved"] for r in reports),
    }
    return ClassedPool(classes=tuple(new_classes)), merged
