"""Where JAX's persistent compilation cache lives.

Call :func:`enable_compile_cache` once at program start-up (the serve
launcher, the benchmark runner and ``chip_smoke.py`` do), never on import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache: fixed, so a later run from the same checkout finds
# what an earlier one compiled (a directory named per run would never hit).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is read by JAX itself and
    nothing is changed here.  Otherwise the cache goes to
    :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
