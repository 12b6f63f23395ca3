"""Where JAX's persistent compilation cache lives.

Entry points call :func:`enable_compile_cache` first thing, before any
program compiles. The directory is part of the cache key's lookup, so it
must not move between runs: it is either the one the environment names
(``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself: this module then
sets nothing) or one fixed path inside the checkout.
"""

from __future__ import annotations

import contextlib
import os
import threading
from pathlib import Path
from typing import Iterator

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"
# two open compiled_afresh() blocks would restore each other's flag
_AFRESH_LOCK = threading.Lock()


def enable_compile_cache() -> str:
    """Place the persistent compile cache; returns the directory in use."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)


@contextlib.contextmanager
def compiled_afresh() -> Iterator[None]:
    """What the block compiles is compiled now, neither read from the
    persistent cache nor written to it.

    For a program whose OUTPUT has a layout of its own. Under jax 0.9.0 on
    a TPU such a program loaded from the persistent cache writes its result
    in the layout it was compiled for, but the array it returns reports the
    device's default layout (and that layout's byte size): the next program
    handed it is compiled for the wrong layout and refused at dispatch
    (PERF.md section 6, PR 27, measured on a v5e). Programs that only READ
    a stated layout come back from the cache sound, and no other backend
    lays an array out by itself, so the one caller
    (``train.step.commit_token_table``) enters this on a TPU only.

    The flag is process wide and JAX re-reads it only after ``reset_cache``:
    a thread that compiles while the block is open compiles afresh too (and
    keeps nothing). The ``Trainer`` commits its table in its constructor,
    before its prefetch and server threads exist. JAX counts no cache miss
    for what is compiled here, so ``compile_cache_misses`` does not see it:
    the ``table_commit`` span says ``compiled_afresh`` and carries the time.
    """
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    with _AFRESH_LOCK:
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()
