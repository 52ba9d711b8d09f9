"""JAX's persistent compilation cache for the entry points.

``enable_compile_cache()`` is called by ``chip_smoke.py`` and by the
``main()`` of each ``repro.launch`` driver — never when the library is
imported.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is
the cache and no other is set.  Otherwise the cache is ``.jax_cache/`` at
the root of the checkout (listed in ``.gitignore``): a fixed path, since
the path is part of what a later process must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` (this file is ``src/repro/launch/...``).
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
