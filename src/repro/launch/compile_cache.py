"""Where JAX's persistent compilation cache lives.

``use_compile_cache()`` is called first thing by the entry points (the
launchers' ``main()``, ``benchmarks/run.py``, ``chip_smoke.py``); nothing
calls it at import time and tests do not call it.  The cache directory is
part of every entry's key, so it must not move between runs:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
  sets nothing;
* otherwise the cache is the fixed ``<checkout>/.jax_cache`` (gitignored).
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
