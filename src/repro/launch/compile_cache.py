"""JAX's persistent compilation cache, kept at one fixed place.

The entry points (``chip_smoke.py``, :mod:`repro.launch.serve_sharded`,
``benchmarks.run``) call :func:`enable_compile_cache` before their first
compile; importing the library never does, and tests do not.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: used when ``JAX_COMPILATION_CACHE_DIR`` is unset.  Fixed, so a later run
#: from the same checkout finds what an earlier one compiled.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turns the persistent compilation cache on and returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read it already and
    that directory stands; otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`.  Every compile is cached, however short: a
    served flush's kernel compiles in well under JAX's default one-second
    floor.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
