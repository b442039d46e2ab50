"""Where JAX keeps its persistent compilation cache, and what its keys hold.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and no
directory is set here.  Otherwise the cache goes to one fixed directory
inside the checkout, `<repo>/.jax_cache` (gitignored): the cache's keys
include its path, so a directory that moves from run to run would never
hit.

Either way the keys include the programs' metadata (op names, source
lines).  By default JAX strips it, and a program that differs from a
cached one only in its `jax.named_scope`s then loads the cached
executable, whose old op names are what a profile shows: a scope added
to the round would never appear in a trace taken against a warm cache.
Source paths enter the metadata relative to the checkout, so two
checkouts of one tree share their entries.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[3]
REPO_CACHE = ROOT / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory, key
    it on the programs' metadata too (see the module docstring), and
    return the directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(ROOT) + os.sep))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
