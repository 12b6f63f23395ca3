"""Where JAX's persistent compilation cache lives.

Entry points call :func:`enable_compile_cache` first thing, before any
program compiles. The directory is part of the cache key's lookup, so it
must not move between runs: it is either the one the environment names
(``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself: this module then
sets nothing) or one fixed path inside the checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Place the persistent compile cache; returns the directory in use."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
