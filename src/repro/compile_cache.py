"""Where JAX keeps its persistent compile cache.

The one place in the repository that places the cache. Entry points that
compile for a chip (``chip_smoke.py``, ``benchmarks/run.py``) call
``configure()`` before their first compile; library code never does.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def configure() -> str:
    """Place the cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, decides: JAX reads it itself,
    so nothing is set here. Otherwise the cache goes to ``.jax_cache`` at
    the root of the checkout — a fixed path, never a temporary one, so a
    second run from the same checkout finds what the first compiled.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
