"""Persistent XLA compile cache for the entry points.

Each entry point (``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``, ``chip_smoke.py``) calls
:func:`enable_compile_cache` when its ``main()`` starts; importing the
library never does.  The rule:

  * ``JAX_COMPILATION_CACHE_DIR`` set — JAX already reads it; nothing is
    configured in code;
  * otherwise — one fixed directory inside the checkout
    (``<repo>/.jax_cache``, listed in ``.gitignore``).  The path is part
    of the cache key, so it is never built from a temporary name, a
    process id or the time.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
CHECKOUT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
