"""Where JAX keeps its persistent compilation cache.

Called from the ``main`` of each entry point, never at import. When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is set
here. Otherwise the cache goes to ``<checkout>/.jax_cache``: a fixed path,
because the directory is part of what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the repository checkout holding ``src/repro``
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax

        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
