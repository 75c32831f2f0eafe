"""Where the entry points keep JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and stands:
no code here sets another directory. Otherwise the cache lives at a fixed
directory inside the checkout (``<repo>/.jax_cache``, gitignored) — never a
temp-, pid- or time-named one, because the path is part of the cache's key
and a directory that moves never hits.

Only the ``__main__`` blocks of the entry points (serve, train, the bench
scripts, ``chip_smoke.py``) call ``use_compile_cache``; importing the
library, and so the test suite, never turns the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
