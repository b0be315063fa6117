"""JAX's persistent compilation cache, placed from outside or at a
fixed path in the checkout."""
from __future__ import annotations

import os
from pathlib import Path

# src/repro/compile_cache.py -> the checkout root
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Called at the start of each entry point's ``main``, never at import.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing else is set.  Otherwise the cache is ``.jax_cache/`` in the
    checkout: a fixed path, because the path is part of what a later
    run must find again (never a temp name, a pid or the time).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
