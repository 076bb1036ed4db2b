"""JAX's persistent compilation cache at a fixed place.

Entry points (``launch/train.py``, ``chip_smoke.py``) call
``enable_compile_cache`` before their first compile, never at import:
tests and library users keep whatever cache policy they set.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
changed here.  Otherwise the cache goes to ``<checkout>/.jax_cache``: a
path built from a temp name, a pid or the time would never be hit again.
"""
from __future__ import annotations

import os
import pathlib

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
