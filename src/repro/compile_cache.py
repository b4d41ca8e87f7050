"""JAX's persistent compilation cache for the program's entry points.

``chip_smoke.py``, the examples and the benchmark harness call
``enable_compile_cache()`` before their first compile. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and this sets nothing.
Otherwise the cache goes to ``.jax_cache/`` at the root of the checkout — a
fixed path, because the path is part of what a later run must match to hit.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
