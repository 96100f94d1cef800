"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so a directory that moves never
hits: the default is a fixed ``.jax_cache/`` at the root of the checkout
(git-ignored).  A deployment that exports ``JAX_COMPILATION_CACHE_DIR``
places it instead; JAX reads that variable itself, so nothing is set in
code then.  The launchers and ``chip_smoke.py`` call
``enable_compile_cache`` before their first compile; tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on (at the checkout default unless the
    environment already places it) and return its directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
