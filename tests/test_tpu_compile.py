"""Ahead-of-time compiles of the serving path's Pallas kernels for a
described (not attached) TPU v5e chip.

Interpret mode accepts what the chip's compiler refuses — unaligned
slices, vector blocks read at a dynamic index, more fast memory than a
kernel may use — so the kernels the serve step calls are compiled here
at olmo-1b's published widths (bf16, H = KH = 16, hd = 128, 64-token
pages) plus one GQA case, and each compiled program must hold the
kernel (``tpu_custom_call``), not a fallback.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and collection runs in every
test worker.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention.kernel import paged_attention_chunk
from repro.kernels.verify_attention.kernel import verify_attention

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
            sds((PAGES, PSZ, KH, hd), jnp.bfloat16),
            sds((PAGES, PSZ, KH, hd), jnp.bfloat16),
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
