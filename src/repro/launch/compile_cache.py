"""JAX's persistent compilation cache for the entry points.

A 32-layer serving program takes tens of seconds to compile; the cache
lets a later process on the same machine load it instead. Each entry
point's ``main()`` calls :func:`enable_compile_cache` — never an import,
so tests and library users keep JAX's defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout's root (``src/repro/launch`` -> three levels up)
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set. Otherwise the cache is ``.jax_cache/`` in the
    checkout, a fixed path that git ignores: the path is part of what
    makes a later run find the entries again.
    """
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
