"""JAX's persistent compilation cache, switched on by the entry points.

`chip_smoke.py`, `benchmarks/*` and `examples/*` call `enable_compile_cache`
before their first compile, so a second run of the same program on the same
machine loads its executables instead of compiling them.  Importing the
library never touches the cache: a test process or a user's program keeps
whatever cache policy it set itself.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache(entry_file: str | os.PathLike) -> str:
    """Persist compiled programs; returns the cache directory in use.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it, and no
    other directory is set here.  Otherwise the cache goes to `.jax_cache`
    (listed in `.gitignore`) in the checkout that holds `entry_file` (the
    caller's `__file__`): the nearest directory above it with a
    `pyproject.toml`.  That is a fixed path, never one built from a temp dir,
    a pid or the time, so every run of the checkout finds the entries of the
    runs before it.
    """
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    here = Path(entry_file).resolve().parent
    root = next(
        (p for p in (here, *here.parents) if (p / "pyproject.toml").is_file()), here
    )
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
