"""JAX's persistent compilation cache, in one place for every entry point.

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: JAX reads
it at start-up and this module sets nothing else.  Otherwise the cache
lives at a fixed directory inside the checkout (gitignored), so a second
run of the same program finds the first run's executables: the cache key
includes nothing of the path, but a directory that moved from run to run
(a temp name, a process id, a time) would never be found again.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use.  Call
    before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
