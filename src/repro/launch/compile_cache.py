"""JAX's persistent compilation cache, for the entry points.

A cache directory is part of each entry's key, so it has to stay at one
path for a later process to find what an earlier one compiled.  Entry
points (``chip_smoke.py``, ``examples/pod_finetune.py``) call
``use_compile_cache()`` from ``main()``; library code and tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps the cache there
    and nothing is set here.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``, which git ignores."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
