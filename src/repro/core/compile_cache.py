"""Where entry points keep JAX's persistent compilation cache.

Called by entry points (``chip_smoke.py``, ``bench/run.py``,
``launch/serve_mr.py``, the examples), never at library import. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing else
is set. Otherwise the cache goes to a fixed ``<checkout>/.jax_cache``: the
path is part of the cache key, so a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
