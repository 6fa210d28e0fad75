"""Where JAX keeps its persistent compilation cache for this checkout.

The entry-point scripts (`chip_smoke.py`, `examples/*.py`,
`benchmarks/*.py`) call `use_persistent_cache()` first thing in their
`main`; the library never does, so importing `repro` changes no JAX
setting.
"""
from __future__ import annotations

import os

import jax

# A fixed path inside the checkout: the cache key includes nothing of the
# path, but a directory that moves between runs is a cache that never hits.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def use_persistent_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    return it. Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads
    it and nothing is changed; otherwise the cache is `.jax_cache/` at the
    root of the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
