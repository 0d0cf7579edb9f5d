"""JAX's persistent compilation cache, placed for the entry points.

The command-line entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.serve``, ``repro.launch.train``) call
:func:`enable_compile_cache` first thing, so a later process of the same
checkout finds the kernels and steps an earlier one compiled.  Importing
:mod:`repro` never touches the cache, so library users and the tests keep
whatever JAX configuration they already have.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_ENV", "DEFAULT_DIR", "enable_compile_cache"]

#: Set by whoever runs the program to place the cache; JAX reads it itself.
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: The checkout's own cache directory (listed in ``.gitignore``).  A fixed
#: path: the directory is part of the cache key, so one that moved per run
#: would never hit.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    nothing else is set here; otherwise the cache goes to
    :data:`DEFAULT_DIR`.
    """
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
