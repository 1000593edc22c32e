"""Where JAX keeps its persistent compilation cache.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so when the variable is set
nothing here overrides it.  Otherwise the cache goes to a fixed directory
inside the checkout, ``<checkout>/.jax_cache``: a path that stays the same
from run to run lets later runs of the same checkout find the entries
earlier runs wrote, and the cache never lands in a shared temp directory.

Entry points call :func:`use_compile_cache` (``chip_smoke.py`` and the
launchers' ``main``); library code and tests never do.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "use_compile_cache"]

CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory: ``$JAX_COMPILATION_CACHE_DIR`` when set (left to JAX),
    else :data:`CHECKOUT_CACHE_DIR`."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
