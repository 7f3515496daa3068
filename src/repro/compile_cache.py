"""Where JAX keeps its persistent compilation cache.

A process that runs on the chip compiles the same programs on every start.
JAX can keep what it compiled on disk and find it again, but only where the
directory stays put from one run to the next.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the checkout's root (``src/repro/compile_cache.py`` -> two levels up)
REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]


def place_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Call it from a program's ``main``, never at import. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
    set here. Otherwise the cache goes to ``<repo root>/.jax_cache``: a
    fixed path, because a cache whose directory changes per run or per
    process is never hit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
