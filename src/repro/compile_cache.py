"""JAX's persistent compilation cache, kept in one fixed directory.

Entry points (``chip_smoke.py``, the benchmarks) call
:func:`enable_compile_cache` before their first compile, so a later run on
the same machine loads what an earlier one compiled.  Importing ``repro``
changes no setting.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

# .jax_cache/ at the root of the checkout (src/repro/ -> ../..): a fixed
# path, since the cache key includes it
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is JAX's own setting and
    wins: no directory is set in code then.  Otherwise the cache lives in
    :data:`CHECKOUT_CACHE_DIR`.  Every compile is cached, however short —
    a serving process compiles many small programs.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
