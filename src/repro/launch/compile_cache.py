"""Persistent XLA compilation cache, placeable from outside the program.

Every entry point (``launch/simulate.py``, ``launch/sim_serve.py``,
``benchmarks/run.py``, ``chip_smoke.py``) calls :func:`enable` before its
first compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads
it and nothing here overrides it. Otherwise the cache lives at a fixed
directory of the checkout, ``.jax_cache/`` next to ``src/``: the directory
is part of the cache key, so it is derived from this file's location and
never from a temporary name, pid or time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
