"""Programs that JAX compiled during set-up because the persistent cache did
not hold them (``/jax/compilation_cache/cache_misses`` events, counted as
``chip_smoke.py`` counts them). Source: program counter. Layer: entry
points. Moves ``setup_s``: in a warm checkout it is 0, and every miss is a
compile that set-up pays."""


def read(run: dict):
    return float(run["cache"]["misses_in_setup"])
