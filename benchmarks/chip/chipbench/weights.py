"""Weights made from ``--seed`` on the device, in one jitted call, in
the type they are served in.

Every projection is drawn from N(0, 1/fan_in), the embedding from
N(0, 1/hidden) so that the tied vocabulary projection of a normalised
state has logits of unit spread, and every norm weight is 1.  Each
stacked leaf is drawn layer by layer (``lax.map``), so the only
float32 temporary is one layer of one leaf.

The same compiled generator gives the program its weights and gives
the reference its own copy, made afresh after the program's state is
freed: the reference never reads what the program holds.
"""

from __future__ import annotations

import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp

#: leaf name -> which size is its fan-in (``None``: a norm, weight 1)
FAN_IN = {"wq": "d", "wk": "d", "wv": "d", "wo": "qdim", "w_gate": "d",
          "w_up": "d", "w_down": "ffn", "tok": "d", "lm_head": "d",
          "scale": None}


def base_key(seed: int) -> jax.Array:
    """A key for any non-negative seed, 64-bit ones included."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _leaf(key, sds, std, stacked: bool):
    if std is None:                          # (1 + scale) norms: weight 1
        return jnp.zeros(sds.shape, sds.dtype)

    def one(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(
            sds.dtype)

    if not stacked:
        return one(key, sds.shape)
    n = sds.shape[0]
    return jax.lax.map(lambda i: one(jax.random.fold_in(key, i),
                                     sds.shape[1:]),
                       jnp.arange(n, dtype=jnp.uint32))


def generator(shapes, sizes: Dict[str, int]) -> Callable:
    """A jitted ``f(key) -> weights`` for the program's parameter tree
    ``shapes`` (ShapeDtypeStructs).  Leaves under a ``groups`` subtree
    carry a leading layer axis.  ``sizes`` gives ``d``, ``qdim`` (heads
    times head size) and ``ffn``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def name(path):
        return [getattr(p, "key", str(p)) for p in path]

    def make(key):
        leaves = []
        for j, (path, sds) in enumerate(flat):
            parts = name(path)
            which = FAN_IN[parts[-1]]
            std = None if which is None else 1.0 / math.sqrt(sizes[which])
            leaves.append(_leaf(jax.random.fold_in(key, j), sds, std,
                                stacked="groups" in parts))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(make)
