"""Where JAX keeps its persistent compilation cache.

A cold TPU compile of a superstep costs seconds to minutes, so entry points
that reach the chip (`chip_smoke.py`, `python -m repro.launch.partition`)
keep compiled programs on disk. The cache key includes the directory, so
the directory is a fixed path: the operator's ``JAX_COMPILATION_CACHE_DIR``
when set (JAX reads it itself; nothing is overridden in code), else
``<checkout>/.jax_cache`` (ignored by git).

The key also holds each operation's metadata, the ``op_name`` that
`repro.obs.annotate`'s named scopes write. JAX leaves it out by default, so
an executable compiled without a scope, or under another one, would be
loaded for a program that has it, and a device profile of that program
would carry stale phase names. The metadata's source files are taken
relative to the checkout, so a copy of the checkout elsewhere still finds
what the first one compiled; an edit that moves a line does not.
"""
from __future__ import annotations

import os
import re

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/utils/compile_cache.py -> the checkout root
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
CHECKOUT_CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory, key
    it on the operations' metadata too, and return the directory."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(CHECKOUT + os.sep))
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
