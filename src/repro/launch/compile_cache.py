"""JAX's persistent compilation cache for the entry points.

``enable_compile_cache()`` is called from each entry point's ``main()`` and
never at import, so tests stay uncached. Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads the directory from it and nothing is set here. Otherwise
the cache lives at ``<checkout>/.jax_cache``: a fixed path, since the path
is part of every entry's key and a moving directory never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
