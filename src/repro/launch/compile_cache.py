"""Where an entry point keeps JAX's persistent compilation cache.

A cache is only found again when it sits at the same path, so the entry
points (``chip_smoke.py``, ``repro.launch.serve_cp``, ``benchmarks/run.py``)
call :func:`use_checkout_cache` before their first compile.  Importing
``repro`` sets nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_checkout_cache() -> str:
    """Point the cache at ``<checkout>/.jax_cache`` unless
    ``JAX_COMPILATION_CACHE_DIR`` is set, in which case JAX already reads it
    and nothing is set here.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
