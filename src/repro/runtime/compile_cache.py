"""JAX's persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``benchmarks/run.py``)
call :func:`enable` once, from their ``main``; importing this module
changes nothing, and the test suite never calls it, so CPU and
ahead-of-time test compiles never land in the cache directory.
"""

from __future__ import annotations

import os
import pathlib

import jax

# A fixed directory inside the checkout (listed in .gitignore): a cache
# that moves between runs never hits, so no temp name, pid or timestamp.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here; otherwise the cache goes to
    :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
