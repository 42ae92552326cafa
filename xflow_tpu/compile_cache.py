"""Where the persistent XLA compile cache lives.

Every entry point that compiles (the CLI, bench.py, __graft_entry__.py)
calls `enable_compile_cache()` once, before its first compile. The
directory is part of every cache key, so it must be the same path in
every process of every run: `JAX_COMPILATION_CACHE_DIR` where the
operator (or the machine) sets it — JAX reads that variable itself, and
this module then sets nothing — and otherwise `.jax_cache/` beside the
package, a path that depends only on where the code is.
"""

from __future__ import annotations

import os

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
