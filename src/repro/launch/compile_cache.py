"""JAX's persistent compilation cache, switched on in one place.

``chip_smoke.py`` and the launchers call ``enable_compile_cache()``
before their first compile, so a later process that compiles the same
program reads it back instead of compiling again.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: ``<repo>/.jax_cache``: a fixed path, so every run finds what an earlier
#: run wrote (this file is ``<repo>/src/repro/launch/compile_cache.py``)
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this function sets nothing. Otherwise the cache goes to
    ``DEFAULT_CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
