"""JAX's persistent compilation cache, placed from outside.

One helper for the scripts that reach the chip (``chip_smoke.py``,
``bench.py``), called before the first compile.  A chip call starts
with no compiled code; where the machine hands later calls the same
``JAX_COMPILATION_CACHE_DIR``, what one call compiled the next finds.
"""

from __future__ import annotations

import os
import pathlib

import jax

_CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it:
    it is left alone and no other directory is set in code.  Where it
    is not, the cache lives at ``<checkout>/.jax_cache`` — a fixed path
    (the path is part of the cache key, so a directory that moves never
    hits).  Every program is kept, however fast it compiled."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(_CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
