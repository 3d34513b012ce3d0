"""Where JAX's persistent compilation cache lives.

Every entry point (`shadow_tpu` run and `serve`, chip_smoke.py, the
benchmark harness) calls `enable_compile_cache()` once before its first
compile; importing the package never does. A set
`JAX_COMPILATION_CACHE_DIR` wins and JAX reads it itself, so no other
directory is set in code. Otherwise the cache lives at one fixed path
in the checkout: the path is part of the cache key, so a directory that
moves between runs never hits.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory.

    JAX's own thresholds stay: only programs that take a second or more
    to compile are cached. Caching every eager op as well flooded the
    cache with thousands of tiny entries, and threads compiling at once
    then waited on its file lock (chip_smoke, PR 21)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    os.makedirs(path, exist_ok=True)
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
