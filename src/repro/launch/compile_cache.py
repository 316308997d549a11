"""Persistent XLA compile cache for the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module changes nothing.  Otherwise the cache lives at ONE fixed path
inside the checkout (:data:`CACHE_DIR`, git-ignored), so a later run of
the same checkout reads what an earlier run compiled — never a temp
name, a pid or a time stamp, which would move the directory and miss.

Only entry points call :func:`enable_compile_cache` (``chip_smoke.py``,
``launch/serve.py``, ``launch/httpd.py``, ``benchmarks/run.py``);
importing a library module never configures a cache.
"""
from __future__ import annotations

import os
import pathlib

#: ``<checkout>/.jax_compile_cache`` (this file is
#: ``<checkout>/src/repro/launch/compile_cache.py``).
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_compile_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at the checkout's fixed
    directory unless ``JAX_COMPILATION_CACHE_DIR`` already names one.
    Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
