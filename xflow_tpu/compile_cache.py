"""Where the persistent XLA compile cache lives.

Every entry point that compiles (the CLI, bench.py, __graft_entry__.py)
calls `enable_compile_cache()` once, before its first compile. The
directory is part of every cache key, so it must be the same path in
every process of every run: `JAX_COMPILATION_CACHE_DIR` where the
operator (or the machine) sets it — JAX reads that variable itself, and
this module then sets no directory — and otherwise `.jax_cache/` beside
the package, a path that depends only on where the code is. Wherever it
lies, the operations' metadata is part of the key.
"""

from __future__ import annotations

import contextlib
import os
import threading

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory.

    The operations' metadata goes into the cache's key. JAX leaves it
    out by default, and a program that differs from a cached one by its
    `jax.named_scope`s alone is then READ from the cache with the other
    commit's `op_name` paths in its text: its compile record's
    `op_scopes` (telemetry.op_phases) would carry labels this checkout
    does not write (tests/test_phase_map.py shows both halves). The
    price: source lines are metadata too, so an edit that moves a step
    builder's lines compiles once more — what a checkout's first run
    pays anyway. An operation's location is held to its innermost frame
    (JAX's default writes ten frames of the call stack into it; the
    switch that drops the stack whole, `jax_include_full_tracebacks_in_
    locations`, drops the scopes from `op_name` with it): with the stack
    in the key, the same program reached from another entry point — the
    trainer's eval and the serve runner share `predict`; the benchmark
    and a script around it — would never share an entry."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR


# `no_persistent_cache` turns a switch of the process: one block at a
# time, re-entered by the thread that is in it, and only the outermost
# exit turns the switch back.
_switch = threading.RLock()
_depth = 0
_was = True


@contextlib.contextmanager
def no_persistent_cache():
    """Compile, inside this block, past the persistent cache: nothing is
    read from it and nothing written.

    For every program that hands back an array in a layout other than
    the device's default (train/engine.py `state_formats`: the sorted
    engines' steps, the placement's relayout). An executable that is
    READ from the cache runs as it was compiled, but the arrays it
    returns report the default layout whatever they are in (JAX 0.9.0 on
    a v5e: `compiled.output_formats` still says the pinned layout, the
    result's `.format` does not). The next program then either refuses
    the array (a pinned input: "compiled for input layouts that
    disagree") or is compiled for a layout the buffer is not in
    (`INVALID_ARGUMENT: ... got buffer with incompatible size`). A
    program whose outputs are all in the default layout, pinned inputs
    or not, is safe to cache (`tools/layout_cache_probe.py`, run twice
    on a TPU, says whether this still holds).

    The switch is the process's (`reset_cache` re-reads it). Blocks on
    two threads take turns, a block inside a block changes nothing, and
    the outermost exit puts back what the first entry found. A compile
    that another thread starts outside any block while one is open
    misses the cache too: slower, never wrong.

    Goes, with `past_cache` and tools/layout_cache_probe.py, when the
    packed table is stored transposed and nothing is pinned any more
    (ROADMAP Queue 1 #2)."""
    global _depth, _was
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    with _switch:
        if _depth == 0:
            _was = jax.config.jax_enable_compilation_cache
            jax.config.update("jax_enable_compilation_cache", False)
            compilation_cache.reset_cache()
        _depth += 1
        try:
            yield
        finally:
            _depth -= 1
            if _depth == 0:
                jax.config.update("jax_enable_compilation_cache", _was)
                compilation_cache.reset_cache()


class _LoweredPastCache:
    """A lowered program whose `compile()` runs under `no_persistent_cache`."""

    def __init__(self, lowered):
        self._lowered = lowered

    def compile(self, *args, **kwargs):
        with no_persistent_cache():
            return self._lowered.compile(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._lowered, name)


class past_cache:
    """A jitted callable whose executables are compiled past the
    persistent cache, one per signature: what a step builder wraps a
    program in that hands back a leaf in a pinned layout
    (`no_persistent_cache` has why). It lowers as the jit object does,
    so `telemetry.CompileRecorder` wraps it like any other and copies
    `persistent_cache` (False) into the program's compile records."""

    persistent_cache = False

    def __init__(self, jitted):
        self._jitted = jitted
        self._compiled: dict = {}

    def lower(self, *args, **kwargs):
        return _LoweredPastCache(self._jitted.lower(*args, **kwargs))

    def __call__(self, *args, **kwargs):
        import jax

        # shape, dtype, and sharding with layout (an executable compiled
        # for one layout refuses an array in another) of every leaf
        leaves, treedef = jax.tree.flatten((args, kwargs))
        key = treedef, tuple(
            (x.shape, x.dtype, getattr(x, "format", None) or getattr(x, "sharding", None))
            if hasattr(x, "shape") and hasattr(x, "dtype") else (type(x), repr(x))
            for x in leaves
        )
        compiled = self._compiled.get(key)
        if compiled is None:
            compiled = self._compiled[key] = self.lower(*args, **kwargs).compile()
        return compiled(*args, **kwargs)
