"""JAX's persistent compilation cache for the repository's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
``enable_compile_cache`` once at start-up; the library never does, on
import or otherwise, and neither do the tests.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache(root: str) -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it at
    import and nothing else is set here.  Otherwise the cache goes to
    ``<root>/.jax_cache``: a fixed path inside the checkout, since the
    path is part of what a later run must find again."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax
    path = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
