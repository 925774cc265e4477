"""Where JAX's persistent compilation cache lives — decided in one place.

Every whole-model program takes minutes to compile, so every entry point that
opens the device (engine server, bench child, the on-chip scripts) shares one
cache. The directory is part of the cache key: it must not move between runs,
so it is never derived from a relative ``__file__``, a pid or a time.
"""

from __future__ import annotations

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
