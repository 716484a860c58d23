"""Placement of JAX's persistent compilation cache for the entry points.

A program that runs on a fresh machine pays every compile again unless
its compiled executables are kept on disk.  ``enable_compile_cache`` is
called at the start of each entry point's main (never at import: tests
and library callers keep the cache off):

  * where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
    and nothing else is set;
  * otherwise the cache goes to ``.jax_cache`` at the root of this
    checkout — a fixed path, because the directory is where later runs
    look, so one that moved (a temp name, a pid, a timestamp) would
    never be hit again.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
