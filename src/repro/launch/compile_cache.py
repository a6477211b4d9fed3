"""JAX persistent compilation cache for the entry points.

``enable()`` is called once at start-up by ``chip_smoke.py`` and the
``launch/*`` drivers.  If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is changed.  Otherwise the cache goes to ``.jax_cache/``
at the root of the checkout: a fixed path, since the directory is part of
the cache key and one that moves never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "enable"]

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
