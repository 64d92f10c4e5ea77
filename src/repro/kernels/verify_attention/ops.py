"""Jit'd public wrapper for speculative verify attention.

Same dispatch contract as kernels.paged_attention.ops: the Pallas
page-grouped kernel on TPU, the pure-jnp reference elsewhere, and
force={"kernel","interpret","ref"} for tests.
"""

from __future__ import annotations

import functools

import jax

from .kernel import verify_attention as _kernel
from .ref import verify_attention_ref as _ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("force",))
def verify_attention(q, k_pages, v_pages, page_table, base_lens,
                     force: str = "auto"):
    """Dispatch: force in {"auto", "kernel", "interpret", "ref"}."""
    if force == "kernel" or (force == "auto" and _on_tpu()):
        return _kernel(q, k_pages, v_pages, page_table, base_lens)
    if force == "interpret":
        return _kernel(q, k_pages, v_pages, page_table, base_lens,
                       interpret=True)
    return _ref(q, k_pages, v_pages, page_table, base_lens)
