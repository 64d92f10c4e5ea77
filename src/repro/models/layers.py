"""Parameter definitions, norms, RoPE, embeddings, dense FFN.

Parameters live in nested dicts of arrays.  Shapes/axes are declared via
:class:`ParamDef` trees so the same declaration yields (a) initialized
arrays, (b) ``jax.ShapeDtypeStruct`` stand-ins for the dry-run, and
(c) ``PartitionSpec`` trees from logical-axis rules
(:mod:`repro.parallel.partition`).
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class ParamDef(NamedTuple):
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]    # logical axis names, len == ndim
    dtype: Any = jnp.float32
    init: str = "normal"               # normal | zeros | ones


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


#: axes that stack independent matrices; they are never contracted
_STACK_AXES = ("layers", "experts")


def fan_in(d: ParamDef) -> int:
    """Inputs each output of ``d`` sums over: its first axis once the
    stacking axes are dropped — both of ``("heads", "head_dim")`` for
    attention's output projection.  Counting a stacking axis instead
    would scale every stacked layer's weights by 1/sqrt(n_layers), and
    attention scores with them into a near one-hot softmax."""
    dims = [(n, a) for n, a in zip(d.shape, d.axes) if a not in _STACK_AXES]
    if not dims:
        return 1
    if dims[0][1] == "heads" and len(dims) > 1:
        return dims[0][0] * dims[1][0]
    return dims[0][0]


def init_tree(key: jax.Array, defs) -> Any:
    """Initialize a pytree of ParamDefs into arrays."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=is_def)
    keys = jax.random.split(key, len(leaves))
    arrs = []
    for k, d in zip(keys, leaves):
        if d.init == "zeros":
            arrs.append(jnp.zeros(d.shape, d.dtype))
        elif d.init == "ones":
            arrs.append(jnp.ones(d.shape, d.dtype))
        else:
            arrs.append(
                (jax.random.normal(k, d.shape, jnp.float32)
                 * (1.0 / math.sqrt(fan_in(d)))).astype(d.dtype))
    return jax.tree.unflatten(treedef, arrs)


def shape_tree(defs) -> Any:
    """ShapeDtypeStruct stand-ins (dry-run path; no allocation)."""
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype), defs, is_leaf=is_def)


# ----------------------------------------------------------------- norms

def rmsnorm(x, scale=None, eps: float = 1e-6):
    """RMSNorm with a bf16 primal chain.

    Only the variance *reduction* runs in f32 (a per-row scalar); the
    elementwise normalize/scale stays in x.dtype.  This keeps the big
    [B,S,d] primals — and therefore their cotangents and any TP
    all-reduce placed on them — in bf16 instead of f32, halving both
    HBM traffic and collective bytes (EXPERIMENTS.md §Perf A2).
    """
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    rs = jax.lax.rsqrt(var + eps).astype(x.dtype)
    y = x * rs
    if scale is not None:
        y = y * (1.0 + scale).astype(x.dtype)
    return y


def ln_nonparam(x, eps: float = 1e-5):
    """OLMo-style non-parametric LayerNorm (bf16 primal chain)."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    rs = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return (x - mu.astype(x.dtype)) * rs


def norm_defs(cfg) -> Dict[str, ParamDef]:
    if cfg.norm == "ln_nonparam":
        return {}
    return {"scale": ParamDef((cfg.d_model,), ("embed",), jnp.float32, "zeros")}


def apply_norm(cfg, params, x):
    if cfg.norm == "ln_nonparam":
        return ln_nonparam(x)
    return rmsnorm(x, params["scale"])


# ----------------------------------------------------------------- rope

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., seq, heads, head_dim]; positions: [..., seq]."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                   # [hd/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- ffn

def ffn_defs(cfg) -> Dict[str, ParamDef]:
    d, f, dt = cfg.d_model, cfg.d_ff, cfg.jdtype
    if cfg.act == "swiglu":
        return {
            "w_gate": ParamDef((d, f), ("embed", "mlp"), dt),
            "w_up": ParamDef((d, f), ("embed", "mlp"), dt),
            "w_down": ParamDef((f, d), ("mlp", "embed"), dt),
        }
    return {
        "w_up": ParamDef((d, f), ("embed", "mlp"), dt),
        "w_down": ParamDef((f, d), ("mlp", "embed"), dt),
    }


def ffn_apply(cfg, params, x):
    if cfg.act == "swiglu":
        h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
    else:
        h = jax.nn.gelu(x @ params["w_up"])
    return h @ params["w_down"]


# ----------------------------------------------------------------- embeds

def embed_defs(cfg) -> Dict[str, ParamDef]:
    dt = cfg.jdtype
    out = {"tok": ParamDef((cfg.vocab, cfg.d_model), ("vocab", "embed"), dt)}
    if not cfg.tie_embeddings:
        out["lm_head"] = ParamDef(
            (cfg.d_model, cfg.vocab), ("embed", "vocab"), dt)
    return out


def embed_apply(params, tokens):
    return params["tok"][tokens]


def logits_apply(cfg, params, x):
    w = params.get("lm_head")
    if w is None:
        w = params["tok"].T
    return x @ w


def logits_argmax_chunked(cfg, params, x, chunk: int = 1024):
    """``jnp.argmax(logits_apply(cfg, params, x), -1)`` without ever
    materializing the [..., V] logits tensor.

    Scans vocabulary chunks keeping a running (max, argmax).  Each
    candidate logit is the same dot product the full projection
    computes, and ascending chunk order with a strict ``>`` preserves
    ``jnp.argmax``'s first-max tie-break — so the winner is exactly the
    full projection's argmax.  The speculative verify path uses this so
    greedy draft verification never builds [T, V] (DESIGN.md §12).
    """
    w = params.get("lm_head")
    if w is None:
        w = params["tok"].T
    D, V = w.shape
    chunk = min(chunk, V)
    n = V // chunk

    def fold(carry, lg, off):
        best, arg = carry
        m = jnp.max(lg, axis=-1)
        a = jnp.argmax(lg, axis=-1).astype(jnp.int32) + off
        upd = m > best
        return jnp.where(upd, m, best), jnp.where(upd, a, arg)

    def body(carry, wc_off):
        wc, off = wc_off
        lg = (x @ wc).astype(jnp.float32)
        return fold(carry, lg, off), None

    ws = w[:, :n * chunk].reshape(D, n, chunk).transpose(1, 0, 2)
    offs = jnp.arange(n, dtype=jnp.int32) * chunk
    init = (jnp.full(x.shape[:-1], -jnp.inf, jnp.float32),
            jnp.zeros(x.shape[:-1], jnp.int32))
    (best, arg), _ = jax.lax.scan(body, init, (ws, offs))
    if V % chunk:
        lg = (x @ w[:, n * chunk:]).astype(jnp.float32)
        best, arg = fold((best, arg), lg, jnp.int32(n * chunk))
    return arg


def chunked_softmax_xent(cfg, embed_params, x, labels, chunk: int = 512):
    """Cross-entropy without materializing [B, S, V] logits.

    Scans over sequence chunks; with remat the chunk logits are
    recomputed in the backward pass.  Returns mean loss over tokens.
    """
    B, S, D = x.shape
    chunk = min(chunk, S)
    n = S // chunk
    xs = x[:, :n * chunk].reshape(B, n, chunk, D).swapaxes(0, 1)
    ys = labels[:, :n * chunk].reshape(B, n, chunk).swapaxes(0, 1)

    def body(acc, xy):
        xc, yc = xy
        logits = logits_apply(cfg, embed_params, xc).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, yc[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(logz - gold), None

    total, _ = jax.lax.scan(body, jnp.float32(0), (xs, ys))
    # remainder (S not divisible by chunk)
    if S % chunk:
        xc, yc = x[:, n * chunk:], labels[:, n * chunk:]
        logits = logits_apply(cfg, embed_params, xc).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        total = total + jnp.sum(logz - gold)
    return total / (B * S)
