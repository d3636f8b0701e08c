"""Persistent compilation cache for the entry points.

A control round runs many small jitted programs (the chain fleet per
bucket shape, the fused sizing table, the surrogate refit, the top-K
selections), and a cold process compiles every one of them.  The entry
points (``chip_smoke.py``, ``benchmarks/run.py``, the benches' ``__main__``)
call :func:`enable_compile_cache` before their first compile so a second
run in the same checkout reads those programs back.  The library never
calls it on import.
"""

from __future__ import annotations

import os

#: In-checkout cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset.  Fixed, because the directory is part of the cache key: a path
#: built from a temp name, a pid or the time would never hit.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX and no other
    directory is set; otherwise the cache lives at :data:`DEFAULT_DIR`.
    Every compile is cached, however short: a round's programs each take
    well under JAX's default one-second threshold.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
