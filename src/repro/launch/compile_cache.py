"""Where JAX keeps its persistent compilation cache.

A cache only pays when a later process finds it again, and the directory is
part of what it looks up, so the path must not move between runs: no
temporary name, no pid, no time.  ``JAX_COMPILATION_CACHE_DIR``, when set,
is read by JAX itself and wins; otherwise the cache lives at a fixed path
inside the checkout (``<repo>/.jax_cache``, gitignored).
"""

from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Call before the first compile of the process.  Sets nothing when
    ``JAX_COMPILATION_CACHE_DIR`` is already set."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
