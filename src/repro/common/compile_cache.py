"""Where JAX keeps its persistent compilation cache.

Call ``enable_compile_cache()`` at the top of an entry point, before the
first compile.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is changed here.  Otherwise the cache goes to
``<repo>/.jax_cache``: a fixed path, because the path is part of what a
later run must find again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
