"""JAX's persistent compilation cache, placed from outside.

Every entry point (``launch/serve.py``, ``benchmarks/run.py``,
``chip_smoke.py``) calls :func:`use_compile_cache` before it compiles.
If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and this sets
nothing else. Otherwise the cache goes to ``.jax_cache/`` at the root
of the checkout: a fixed path, because the path is part of what makes
a later run find an entry.

:func:`backend_compiles` counts the process's backend compiles (a
program loaded from the cache is not one), so a server can say whether
it compiled while it served.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"

#: the jax.monitoring duration event JAX records once per backend compile
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# JAX's monitoring listeners are process-wide and are never removed, so
# the count is too: None until the listener is registered
_compiles = None

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


def backend_compiles() -> int:
    """Backend compiles in this process since the first call, which
    registers the process-wide ``jax.monitoring`` listener that counts
    them; callers take the difference of two readings."""
    global _compiles
    if _compiles is None:
        import jax

        def count(event, duration_secs, **kw):
            global _compiles
            if event == COMPILE_EVENT:
                _compiles += 1

        _compiles = 0
        jax.monitoring.register_event_duration_secs_listener(count)
    return _compiles
