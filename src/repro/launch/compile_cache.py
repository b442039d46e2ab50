"""Where JAX keeps its persistent compilation cache.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing
is set here.  Otherwise the cache goes to one fixed directory inside the
checkout, `<repo>/.jax_cache` (gitignored): the cache's keys include its
path, so a directory that moves from run to run would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory (see
    the module docstring) and return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
