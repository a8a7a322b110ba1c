"""JAX's persistent compilation cache for the entry points.

Scripts (``chip_smoke.py``, the examples) call
:func:`enable_compile_cache` first thing, so a second run of the same
program on the same device reuses its compiled kernels.  The library never
sets a cache on import: the tests run without one.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: environment variable JAX itself reads the cache directory from
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: default cache directory: fixed inside the checkout (git-ignored), since
#: a cache whose path moves from run to run never hits
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    A directory named by ``JAX_COMPILATION_CACHE_DIR`` is left to JAX,
    which reads the variable itself; otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
