"""JAX's persistent compilation cache at one fixed place.

Every entry point (``chip_smoke.py``, ``repro.launch.sweep``,
``repro.launch.train``, ``repro.launch.fl_spmd``, ``benchmarks/run.py``)
calls :func:`enable_compile_cache` at the start of its ``main()``; nothing
calls it at import.  The cache directory is part of what a cached program
is found by, so it never moves between runs:

* ``JAX_COMPILATION_CACHE_DIR`` when it is set (and no other directory);
* otherwise ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""
from __future__ import annotations

import os

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
