"""JAX's persistent compilation cache, placed from outside.

Entry points (`chip_smoke.py`, `repro.launch.train`, `repro.launch.serve`)
call `enable()` once, before their first compile; nothing calls it at
import time. Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself
and this module sets no other directory. Otherwise the cache goes to the
fixed `.jax_cache/` at the root of the checkout (git ignores it): a fixed
path, because the path is part of what a cache hit depends on, so a
directory named after a pid, a time or a temporary name would never hit.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def cache_dir() -> str:
    """Where `enable()` puts the cache: the environment's choice, else
    `<checkout>/.jax_cache`."""
    return os.environ.get(ENV) or os.path.join(ROOT, ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
