"""Where JAX keeps its persistent compilation cache.

Program entry points call :func:`enable_compile_cache` from ``main``,
never at import, so importing the package leaves JAX's configuration
alone.
"""
from __future__ import annotations

import os

import jax

#: The checkout holding this package: ``<root>/src/repro/launch/``.
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory: JAX reads
    it itself and no other is set here.  Otherwise the cache goes to
    ``.jax_cache/`` at the checkout root, a fixed path, so one checkout's
    runs find each other's compiled programs.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
