"""Ahead-of-time compiles of the serving path for a described (not
attached) TPU v5e chip.

Interpret mode accepts what the chip's compiler refuses — unaligned
slices, vector blocks read at a dynamic index, more fast memory than a
kernel may use — so the kernels the serve step calls are compiled here
at olmo-1b's published widths (bf16, H = KH = 16, hd = 128, 64-token
pages) plus one GQA case, and each compiled program must hold the
kernel (``tpu_custom_call``), not a fallback.

The layer scan of the serve step is compiled too, at olmo-1b's widths
with 4 layers, and must read and write the stacked KV pool in place:
no op may copy, transpose or slice a pool-sized array, the KV write's
scatter is the one pool-sized fusion, and the temporaries stay below
one layer's pool (DESIGN.md §3).

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and collection runs in every
test worker.
"""

import dataclasses
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import models
from repro.configs import get_config
from repro.kernels.paged_attention import ops as paged_ops
from repro.kernels.paged_attention.kernel import paged_attention_chunk
from repro.kernels.verify_attention.kernel import verify_attention
from repro.models import transformer as tfm

# a serve step at --b-local 8 --max-len 1024: 8 lanes, 16 pages each
B, PSZ, MAXP, PAGES = 8, 64, 16, 8 * 16 + 8


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, T, H, KH, hd):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    args = (sds((B, T, H, hd), jnp.bfloat16),
            sds((PAGES, KH, PSZ, hd), jnp.bfloat16),
            sds((PAGES, KH, PSZ, hd), jnp.bfloat16),
            sds((B, MAXP), jnp.int32),
            sds((B,), jnp.int32))
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("H,KH,hd", [(16, 16, 128), (32, 8, 64)],
                         ids=["olmo1b", "gqa"])
@pytest.mark.parametrize("T", [1, 8])
def test_paged_attention_chunk_compiles(one_chip, T, H, KH, hd):
    hlo = _compiled_text(paged_attention_chunk, one_chip, T, H, KH, hd)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("H,KH,hd", [(16, 16, 128), (32, 8, 64)],
                         ids=["olmo1b", "gqa"])
def test_verify_attention_compiles(one_chip, H, KH, hd):
    # draft_len 4: a verify lane is 1 committed + 4 draft positions
    hlo = _compiled_text(verify_attention, one_chip, 5, H, KH, hd)
    assert "tpu_custom_call" in hlo


# an instruction with an array result: name, dims, opcode; and the
# header of a computation, whose ROOT instruction follows in its body
_INSTR = re.compile(r"^\s*(ROOT\s+)?%([\w.\-]+)\s*=\s*\w+\[([\d,]*)\]\S*"
                    r"\s+([\w\-]+)\(")
_COMP = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s")
_CALLS = re.compile(r"calls=%([\w.\-]+)")


def _pool_sized_ops(hlo: str, elems: int):
    """(opcode, name, callee) of every instruction, fused computations
    included, whose array result holds at least ``elems`` elements;
    and each computation's ROOT opcode."""
    big, roots, comp = [], {}, None
    for line in hlo.splitlines():
        head = _COMP.match(line)
        if head:
            comp = head.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        if m.group(1):
            roots[comp] = m.group(4)
        n = math.prod(int(d) for d in m.group(3).split(",") if d)
        if n >= elems:
            callee = _CALLS.search(line)
            big.append((m.group(4), m.group(2),
                        callee.group(1) if callee else None))
    return big, roots


@pytest.mark.parametrize("T", [1, 64])
def test_layer_scan_reads_the_kv_pool_in_place(one_chip, monkeypatch, T):
    """The serve step's body (``forward_decode_chunk``, the Pallas chunk
    kernel forced) at olmo-1b widths with 4 layers, 8 lanes and
    max_len 1024: a slice, transpose or write-back of the pool, or a
    scatter form that makes XLA relayout the stack, shows up here as a
    pool-sized copy, transpose, dynamic-slice or dynamic-update-slice,
    a pool-sized fusion that is not the KV write, or temporaries."""
    cfg = dataclasses.replace(get_config("olmo-1b"), n_layers=4)
    monkeypatch.setattr(tfm, "paged_attention_chunk", functools.partial(
        paged_ops.paged_attention_chunk, force="kernel"))

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = on_chip(models.param_shapes(cfg))
    state = on_chip(tfm.decode_state_defs(cfg, 1, B, 1024, chunk=64))
    toks = jax.ShapeDtypeStruct((1, B, T), jnp.int32, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((1, B), jnp.int32, sharding=one_chip)
    pool = state.kv_pages["pos0"][0]
    assert pool.shape == (4, 1, 176, 16, PSZ, 128)   # head-major

    def step(params, toks, state, lens):
        return tfm.forward_decode_chunk(cfg, params, toks, state, lens)

    compiled = jax.jit(step, donate_argnums=(2,)).lower(
        params, toks, state, lens).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    layer = math.prod(pool.shape[2:])             # one layer's K elements
    big, roots = _pool_sized_ops(hlo, layer)
    moves = [(op, name) for op, name, _ in big if op in (
        "copy", "transpose", "dynamic-slice", "dynamic-update-slice")]
    assert not moves, f"pool-sized relayout ops: {moves}"
    fusions = [(name, roots.get(callee)) for op, name, callee in big
               if op == "fusion"]
    assert fusions and all(root == "scatter" for _, root in fusions), (
        f"pool-sized fusions and their roots: {fusions}")
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2 * layer * pool.dtype.itemsize, temp
