"""JAX's persistent compilation cache at one fixed place.

Every entry point that compiles (``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.tune``, ``python -m repro.worker``) calls
:func:`enable_compile_cache` before its first compile. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this sets
nothing. Otherwise the cache goes to ``<checkout>/.jax_cache``: a fixed
path, since the path is part of what a later run must find again.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    import jax
    path = os.environ.get(ENV)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
