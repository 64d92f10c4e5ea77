"""Persistent compilation cache placement for every entry point.

A cold chip run compiles each serve-step variant (one per lane width,
crossed with the sampler/speculation flags) from scratch; the
persistent cache lets later processes on the same disk skip that.  A
later process finds only what an earlier one left in the same place,
so the directory must not move between runs: never a temp name, a pid
or a timestamp.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` (gitignored): src/repro/launch/ -> checkout
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def init_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (JAX reads
    it itself) and nothing is set in code.  Otherwise the cache goes to
    the fixed :data:`DEFAULT_DIR`.  Call before the first compilation:
    JAX decides whether a process uses the cache once, at its first
    compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
