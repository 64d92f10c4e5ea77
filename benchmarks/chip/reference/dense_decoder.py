"""Plain float32 reference of a dense pre-norm decoder (OLMo, Phi-3/4
family): embedding, then per layer norm -> attention with RoPE and
grouped KV heads -> residual -> norm -> SwiGLU -> residual, a final
norm and the vocabulary projection.  Straight ``jax.numpy`` at the
highest matmul precision, no cache, no batching tricks, no kernels, and
nothing imported from the program under test.

It follows the published description of the configuration it is given
(key names as in the published ``config.json``):

* ``model_type == "olmo"``: non-parametric LayerNorm, eps 1e-5;
  otherwise RMSNorm with ``rms_norm_eps`` and a weight;
* RoPE on the first ``partial_rotary_factor * head_dim`` dimensions of
  each head, rotate-half convention, base ``rope_theta``;
* query head ``h`` reads KV head ``h // (heads / kv_heads)``.

Weights come from an accessor ``w`` with ``embed() -> [V, d]``,
``head() -> [V, d]`` (the embedding itself when tied),
``final_norm() -> [d] | None`` and ``layer(i) -> dict`` of ``wq [d,
H*hd]``, ``wk``/``wv [d, KH*hd]``, ``wo [H*hd, d]``, ``wg``/``wu [d, f]``,
``wd [f, d]``, ``ln1``/``ln2 [d] | None``.  The model runs one layer at a
time, so only one layer's float32 weights are on the device at once.

``quant`` ("fp8" or "int8") computes the same model in a lower
precision, the control: every matmul input and weight, the embedding
rows and the cached K and V rounded to float8 e4m3 or int8 with one
scale per output channel (weights), token (activations) or token and
head (K and V).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _lowp(x, axis, quant):
    """``x`` rounded to ``quant`` with one scale per slice along
    ``axis`` (returned in float32): symmetric int8, or float8 e4m3."""
    if quant is None:
        return x
    top = {"int8": 127.0, "fp8": 448.0}[quant]
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    s = jnp.where(s == 0, 1.0, s)
    if quant == "int8":
        return jnp.round(x / s) * s
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    return _lowp(x, -1, quant) @ _lowp(w, 0, quant)


def _norm(cfg, x, weight):
    if cfg.get("model_type") == "olmo":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5)
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                          + cfg.get("rms_norm_eps", 1e-6))
    return y if weight is None else y * weight


def _rope(cfg, x, pos):
    """x: [S, heads, hd]; pos: [S]."""
    hd = x.shape[-1]
    r = int(hd * cfg.get("partial_rotary_factor", 1.0))
    inv = 1.0 / cfg.get("rope_theta", 10000.0) ** (
        jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = pos[:, None].astype(jnp.float32) * inv          # [S, r/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def _layer_one(cfg, quant, x, lw):
    """One layer over one sequence.  x: [S, d] float32."""
    S, _ = x.shape
    H, KH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // H
    pos = jnp.arange(S)
    h = _norm(cfg, x, lw["ln1"])
    q = _rope(cfg, _mm(h, lw["wq"], quant).reshape(S, H, hd), pos)
    k = _rope(cfg, _mm(h, lw["wk"], quant).reshape(S, KH, hd), pos)
    v = _mm(h, lw["wv"], quant).reshape(S, KH, hd)
    k, v = _lowp(k, -1, quant), _lowp(v, -1, quant)
    k = jnp.repeat(k, H // KH, axis=1)
    v = jnp.repeat(v, H // KH, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(S, H * hd)
    x = x + _mm(o, lw["wo"], quant)
    h = _norm(cfg, x, lw["ln2"])
    f = jax.nn.silu(_mm(h, lw["wg"], quant)) * _mm(h, lw["wu"], quant)
    return x + _mm(f, lw["wd"], quant)


@functools.lru_cache(maxsize=None)
def _compiled(cfg_items, quant):
    cfg = dict(cfg_items)

    @jax.jit
    def layer(x, lw):                                     # x: [B, S, d]
        return jax.lax.map(lambda xi: _layer_one(cfg, quant, xi, lw), x)

    @jax.jit
    def embed(table, tokens):
        return _lowp(table[tokens].astype(jnp.float32), -1, quant)

    @jax.jit
    def finish(x, weight, rows, cols):
        return _norm(cfg, x[rows, cols], weight)          # [N, d]

    return layer, embed, finish


def _frozen(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))))


def hidden(cfg, w, tokens, rows, cols, quant=None):
    """Final normalised hidden states ``[N, d]`` at positions
    ``(rows[j], cols[j])`` of ``tokens [B, S]``, computed causally over
    each row.  Padding after a row's end does not reach its positions."""
    layer, embed, finish = _compiled(_frozen(cfg), quant)
    with jax.default_matmul_precision("highest"):
        x = embed(w.embed(), tokens)
        for i in range(cfg["num_hidden_layers"]):
            x = layer(x, w.layer(i))
        return finish(x, w.final_norm(), rows, cols)


@functools.partial(jax.jit, static_argnames=("quant", "block"))
def _gaps(h, table, served, quant=None, block=256):
    """Per position of ``h [N, d]``: the best logit of ``h @ table.T``
    minus the logit of ``served``, and the argmax; in blocks of rows so
    that the [N, V] logits never exist at once.  With ``quant`` the
    projection's inputs are first rounded to that precision."""
    n = h.shape[0]
    pad = (-n) % block
    h = _lowp(h, -1, quant)
    t = _lowp(table.astype(jnp.float32), -1, quant)
    hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, block, h.shape[1])
    sb = jnp.pad(served, (0, pad)).reshape(-1, block)

    def body(args):
        hx, sx = args
        logits = hx @ t.T                                 # [block, V]
        got = jnp.take_along_axis(logits, sx[:, None], -1)[:, 0]
        return (jnp.max(logits, -1) - got,
                jnp.argmax(logits, -1).astype(jnp.int32))

    g, a = jax.lax.map(body, (hb, sb))
    return g.reshape(-1)[:n], a.reshape(-1)[:n]


def logits_gap(w, h, tokens):
    """``best - logit[tokens]`` of the reference logits at hidden states
    ``h [N, d]``, and the reference argmax at each."""
    with jax.default_matmul_precision("highest"):
        return _gaps(h, w.head(), tokens)


def argmax(w, h, quant=None):
    """The token each of ``h [N, d]`` puts first, with the vocabulary
    projection in ``quant``."""
    with jax.default_matmul_precision("highest"):
        return _gaps(h, w.head(), jnp.zeros(h.shape[0], jnp.int32),
                     quant=quant)[1]
