"""Where the program keeps what it makes at run time: inside its checkout.

Nothing here runs at import.  Entry points call
:func:`enable_compile_cache` first thing in ``main``.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]

# JAX's persistent compilation cache; listed in .gitignore
COMPILE_CACHE = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set here.  Otherwise the cache lives at the
    fixed ``<checkout>/.jax_cache``: the path is part of what a cached
    entry is found by, so it must not move between runs.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE))
    return str(COMPILE_CACHE)
