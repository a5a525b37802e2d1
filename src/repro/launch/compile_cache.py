"""Persistent compilation cache for the entry points.

A cold start on the chip compiles every program again: the reservoir and
Gram kernels, the streaming fit, both serving step variants.  Entry points
(``chip_smoke.py``, ``launch/serve_dfr.py``, ``benchmarks/run.py``) call
``enable_compile_cache()`` at the top of ``main`` so later runs find their
executables on disk; importing the library never turns the cache on.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this
sets nothing.  Otherwise the cache lives in ``.jax_cache/`` at the root of
the checkout: a fixed path, because the path is part of the cache key.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
