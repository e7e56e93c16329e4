"""JAX's persistent compile cache, kept in one fixed place.

A cold process compiles every jitted step again; the persistent cache
lets the next process read the programs back -- but only if it looks in
the same directory, so the path must not change between runs: no temp
names, pids or times.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself, and
  this module sets no other directory.
* Unset: the cache goes to ``.jax_cache`` at the root of the checkout
  (listed in ``.gitignore``).

Call :func:`enable_compile_cache` from a program's ``main``, never at
import time: importing a module must not change global JAX config.
"""
from __future__ import annotations

import os
import pathlib

__all__ = ["ENV_VAR", "REPO_CACHE_DIR", "compile_cache_dir",
           "enable_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the persistent compile cache uses."""
    return os.environ.get(ENV_VAR) or str(REPO_CACHE_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
