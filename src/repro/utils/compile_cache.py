"""Where JAX keeps its persistent compilation cache.

A cold TPU compile of a superstep costs seconds to minutes, so entry points
that reach the chip (`chip_smoke.py`, `python -m repro.launch.partition`)
keep compiled programs on disk. The cache key includes the directory, so
the directory is a fixed path: the operator's ``JAX_COMPILATION_CACHE_DIR``
when set (JAX reads it itself; nothing is overridden in code), else
``<checkout>/.jax_cache`` (ignored by git).
"""
from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/utils/compile_cache.py -> the checkout root
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    import jax

    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
