"""Placement of JAX's persistent compilation cache.

Entry points call :func:`use_compile_cache` first thing in ``main()``.
Nothing calls it at import time, so tests and library users compile
without a cache on disk unless they ask for one.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed and inside the checkout (listed in .gitignore): a later run from
# the same checkout finds what an earlier one compiled
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no directory is set here. Otherwise the cache goes to
    ``<repo>/.jax_cache``. Every program is cached, not only those that
    took a second or more to compile: a full-width serving run compiles
    many small admission and lookup programs too.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
