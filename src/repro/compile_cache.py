"""Where JAX keeps its persistent compilation cache for this repo's scripts.

``chip_smoke.py`` and the benchmark entry points call
:func:`enable_compile_cache` once, before they compile anything, so a
second run in the same checkout reuses the first run's executables instead
of compiling the UNet steps cold.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
  this module sets no other directory.
* Otherwise the cache lives at ``<repo>/.jax_cache`` (git-ignored).  The
  path is fixed — the cache directory is part of what makes an entry found
  again — so it never holds a temporary name, a process id or a time.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


__all__ = ["enable_compile_cache", "DEFAULT_DIR", "ENV_VAR"]
