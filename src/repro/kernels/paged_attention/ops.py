"""Jit'd public wrapper for paged decode attention.

Selects the Pallas kernel on TPU and the pure-jnp reference elsewhere
(including the CPU dry-run); both share the exact semantics, which the
kernel test suite asserts over shape/dtype sweeps in interpret mode.
"""

from __future__ import annotations

import functools

import jax

from .kernel import paged_attention as _kernel
from .kernel import paged_attention_chunk as _chunk_kernel
from .ref import paged_attention_chunk_ref as _chunk_ref
from .ref import paged_attention_ref as _ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("force",))
def paged_attention(q, k_pages, v_pages, page_table, seq_lens,
                    force: str = "auto"):
    """Dispatch: force in {"auto", "kernel", "interpret", "ref"}."""
    if force == "kernel" or (force == "auto" and _on_tpu()):
        return _kernel(q, k_pages, v_pages, page_table, seq_lens)
    if force == "interpret":
        return _kernel(q, k_pages, v_pages, page_table, seq_lens,
                       interpret=True)
    return _ref(q, k_pages, v_pages, page_table, seq_lens)


@functools.partial(jax.jit, static_argnames=("force",))
def paged_attention_chunk(q, k_pages, v_pages, page_table, base_lens,
                          force: str = "auto"):
    """Chunked-prefill variant; same dispatch contract as above.

    q: [B, T, H, hd]; base_lens: sequence lengths before the chunk.
    """
    if force == "kernel" or (force == "auto" and _on_tpu()):
        return _chunk_kernel(q, k_pages, v_pages, page_table, base_lens)
    if force == "interpret":
        return _chunk_kernel(q, k_pages, v_pages, page_table, base_lens,
                             interpret=True)
    return _chunk_ref(q, k_pages, v_pages, page_table, base_lens)
