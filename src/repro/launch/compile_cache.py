"""Persistent JAX compilation cache for the repository's entry points.

Entry points (``chip_smoke.py``, the examples, ``benchmarks/run.py``) call
:func:`enable_compile_cache` before their first compile; importing this
module changes nothing. The cache directory is part of each entry's key, so
it is a fixed path: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it
itself), otherwise ``.jax_cache/`` at the root of this checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["compile_cache_dir", "enable_compile_cache"]

# src/repro/launch/compile_cache.py -> the checkout root.
_CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The cache directory: ``$JAX_COMPILATION_CACHE_DIR`` if set, else the
    fixed in-checkout ``.jax_cache``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(_CHECKOUT_CACHE)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
