"""JAX's persistent compilation cache, placed from outside.

Every entry point (``launch/serve.py``, ``benchmarks/run.py``,
``chip_smoke.py``) calls :func:`use_compile_cache` before it compiles.
If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and this sets
nothing else. Otherwise the cache goes to ``.jax_cache/`` at the root
of the checkout: a fixed path, because the path is part of what makes
a later run find an entry.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"

#: the checkout's own cache directory (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax
    path = os.environ.get(ENV)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
