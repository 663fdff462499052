"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`use_compile_cache` once, before they compile;
importing this module (or any other of the package) sets nothing. A
cached program is keyed on, among other things, the cache directory, so
the directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it, else ``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    JAX reads ``JAX_COMPILATION_CACHE_DIR`` by itself; when it is set,
    nothing is overridden here."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
