"""Where JAX keeps its persistent compilation cache.

Entry points (the launchers and ``chip_smoke.py``) call
:func:`use_compile_cache` once when they start, never at import.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
overrides it.  Otherwise the cache goes to ``.jax_cache`` at the root of the
checkout: a fixed path, because the path is part of the cache's key, so a
directory named after a temp dir, a process id or the time would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "use_compile_cache"]

#: the checkout-local default (git-ignored)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
