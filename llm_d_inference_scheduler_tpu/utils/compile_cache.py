"""Where JAX's persistent compilation cache lives — decided in one place.

Every whole-model program takes minutes to compile, so every entry point that
opens the device (engine server, bench child, the on-chip scripts) shares one
cache. The directory is part of the cache key: it must not move between runs,
so it is never derived from a relative ``__file__``, a pid or a time.
"""

from __future__ import annotations

import contextlib
import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache, from the package's own absolute location (listed in
# .gitignore). The same path whatever the working directory is.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX at the shared cache and return the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    directory is set in code, so whoever runs the program can place the
    cache. Either way a program's metadata is part of its key: JAX leaves it
    out by default, and an executable served from the cache then carries the
    metadata of whichever tree compiled it first -- other source lines, and
    none of the blocks' scope names (models/scopes.py) where that tree had
    none, which is what a device trace is booked by
    (chipbench/trace_scopes.py). Call before the first compile."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


@contextlib.contextmanager
def outside_compile_cache():
    """Compile what runs inside with the persistent cache off: neither read
    from it nor written to it. For a program whose RESULT has a layout of
    its own (``jax.experimental.layout``): such an executable, read back from
    the cache, writes its rows in that layout into an array that says it has
    the default one -- the values come out in another order, silently
    (jax 0.9.0, libtpu 0.0.34; seen on a v5e, PERF.md section 6, PR 57). A
    program that only TAKES an argument in such a layout comes back right.
    The switch is the process's, not a thread's: for start-up code."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()     # (whether it is used is remembered)
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()
