"""JAX's persistent compilation cache for the command-line entry points.

Every process starts with no compiled code, and on a chip compiling the
solve loops is a large part of a cold start.  The entry points
(``chip_smoke.py``, ``python -m repro.launch.serve_graph``,
``python -m repro.launch.train``) call :func:`enable_compile_cache` before
their first compile, so a second run on the same machine loads executables
instead of compiling them.  Library code and the tests never call it.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

#: The fixed default location: ``<checkout>/.jax_cache`` (git-ignored).  The
#: path is part of every cache key, so it never holds a temporary name, a
#: process id or a time.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and no
    other directory is set here.  Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.
    """
    import jax

    # Every compile is worth keeping: a cold chip call pays for all of them.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
