"""The work a served step needs, counted from the configuration and the
lanes' context lengths -- never from the kernel's grid, padded pages or
the compiled program, so that it reads the same whatever implements it.

A *lane* is what one sequence feeds in one step: ``n`` tokens whose
last one sits at context length ``ctx`` (a prefill chunk, or ``n = 1``
for a decode token).  Token ``i`` of the lane attends to
``ctx - n + i + 1`` positions.
"""

from __future__ import annotations

from typing import Iterable, NamedTuple, Tuple


class Dims(NamedTuple):
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    act_bytes: int      # bytes of one cached K or V element


def dims(cfg: dict) -> Dims:
    """Sizes from a configuration file (published key names)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return Dims(layers=cfg["num_hidden_layers"], d=d, heads=h,
                kv_heads=cfg.get("num_key_value_heads", h),
                head_dim=cfg.get("head_dim") or d // h,
                ffn=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                act_bytes=2 if cfg.get("torch_dtype", "bfloat16")
                in ("bfloat16", "float16") else 4)


def matmul_flops_per_token(c: Dims) -> int:
    """Projection and SwiGLU FLOPs of one token through every layer."""
    qo = 2 * c.d * c.heads * c.head_dim            # wq and wo
    kv = 2 * c.d * c.kv_heads * c.head_dim         # wk and wv
    ffn = 3 * c.d * c.ffn                          # gate, up, down
    return 2 * c.layers * (qo + kv + ffn)


def head_flops(c: Dims) -> int:
    """The vocabulary projection of one token whose logits are needed."""
    return 2 * c.d * c.vocab


def lane_ctx_sum(n: int, ctx: int) -> int:
    """Positions attended to, summed over the lane's ``n`` tokens."""
    return n * ctx - n * (n - 1) // 2


def lane_attn(c: Dims, n: int, ctx: int) -> Tuple[int, int]:
    """(FLOPs, bytes) of attention for one lane in one layer: QK and PV
    over the live context, K and V of the live tokens read once, and
    the lane's queries read and outputs written once."""
    flops = 4 * c.heads * c.head_dim * lane_ctx_sum(n, ctx)
    kv = 2 * ctx * c.kv_heads * c.head_dim * c.act_bytes
    qo = 2 * n * c.heads * c.head_dim * c.act_bytes
    return flops, kv + qo


def step_attn(c: Dims, lanes: Iterable[Tuple[int, int]]) -> Tuple[int, int]:
    """(FLOPs, bytes) of one attention-kernel call: every lane of a step
    in one layer."""
    f = b = 0
    for n, ctx in lanes:
        lf, lb = lane_attn(c, n, ctx)
        f, b = f + lf, b + lb
    return f, b


def attn_least_s(c: Dims, steps: Iterable[Iterable[Tuple[int, int]]],
                 peak_flops: float, peak_bw: float) -> Tuple[float, float]:
    """Least device time the attention kernel could take over these
    steps (one call per layer per step, each bound by the larger of its
    FLOPs over peak and bytes over bandwidth), and the share of that
    time which the bandwidth bound sets."""
    total = by_bw = 0.0
    for lanes in steps:
        f, b = step_attn(c, lanes)
        t_f, t_b = f / peak_flops, b / peak_bw
        total += c.layers * max(t_f, t_b)
        if t_b >= t_f:
            by_bw += c.layers * t_b
    return total, (by_bw / total if total else 0.0)


def model_flops(c: Dims, steps: Iterable[Iterable[Tuple[int, int]]],
                emitted: int) -> int:
    """Model FLOPs of every token the steps processed (projections,
    SwiGLU and attention over its live context) plus the vocabulary
    projection of every token emitted."""
    total = head_flops(c) * emitted
    per_tok = matmul_flops_per_token(c)
    for lanes in steps:
        for n, ctx in lanes:
            total += per_tok * n
            total += c.layers * 4 * c.heads * c.head_dim * lane_ctx_sum(n, ctx)
    return total
