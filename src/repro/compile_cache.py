"""Where JAX keeps its persistent compilation cache.

When ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
nothing is set in code.  Otherwise the cache lives in ``.jax_cache/`` at
the root of the checkout (listed in ``.gitignore``): a fixed path, since
the path is part of what makes a later run find an entry, and inside the
checkout, since the program writes nothing outside it.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> None:
    """Point jax's persistent compilation cache at :data:`CACHE_DIR`
    unless ``JAX_COMPILATION_CACHE_DIR`` already places it."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
