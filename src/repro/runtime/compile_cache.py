"""JAX's persistent compilation cache, for the program's entry points.

A cold run on the chip spends much of its time compiling. The entry points
(``chip_smoke.py``, ``benchmarks/run.py``, ``launch/serve.py`` and the
cluster worker's ``main``) call :func:`enable_compile_cache` before their
first compile, so repeat runs, and processes started together, reuse what
was compiled. Importing the library never turns the cache on.

The directory is part of what the cache is keyed on, so it never moves:
``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads the variable itself, and
nothing else is set here), else ``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    configured = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if configured:
        return configured
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
