"""Where JAX's persistent compilation cache lives.

The entry points (``chip_smoke.py``, ``python -m repro.serve``,
``python -m repro.obs``) call :func:`enable_compile_cache` from their
``main``; nothing calls it at
import, so the library and the tests never touch a cache as a side effect.

``JAX_COMPILATION_CACHE_DIR``, where it is set, wins: JAX reads it itself
and this module sets nothing. Otherwise the cache goes to one fixed
directory inside the checkout, :data:`CACHE_DIR`. The path is part of what
a later process must find again, so it is never temporary, per-process or
time-stamped.
"""

from __future__ import annotations

import os
import pathlib

__all__ = ["CACHE_DIR", "enable_compile_cache"]

# <checkout>/.jax_cache — this file is <checkout>/src/repro/runtime/
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
