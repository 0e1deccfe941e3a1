"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.tsne_run``,
``examples/mouse_pipeline.py``, ``benchmarks.run``) call
:func:`enable_compile_cache` before their first compile; the library itself
never turns the cache on.  The directory is part of each entry's key, so it
is a fixed path, never a temporary name.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (git-ignored)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory.

    If ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads the cache
    from there and no other directory is set.  Otherwise the cache goes to
    :data:`DEFAULT_DIR`.
    """
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
