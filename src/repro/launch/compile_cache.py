"""JAX persistent compilation cache, placed by the entry points only.

``chip_smoke.py``, ``launch/serve.py`` and ``benchmarks/run.py`` call
``use_compile_cache()`` at start-up; nothing calls it on import or under
the tests.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and no other directory is set.  Otherwise the cache lives at a
fixed path inside the checkout, ``<checkout>/.jax_cache`` (git-ignored):
the path is part of what a later run must find again, so it never holds
a temporary name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
