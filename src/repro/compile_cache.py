"""JAX's persistent compilation cache, placed for the entry points.

Entry points (``chip_smoke.py``, ``repro.launch.train``,
``benchmarks.run``, ``examples/*``) call ``enable()`` before their first
compile; importing the library never does. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here. Otherwise the cache lives at a fixed path inside the checkout
(``<repo>/.jax_cache``, git-ignored): the directory is part of what a
later run must find, so it is never built from a temporary name, a pid
or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
