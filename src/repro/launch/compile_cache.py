"""Where JAX's persistent compilation cache lives.

``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads it
itself and no other directory is set in code.  Otherwise the cache is
``.jax_cache`` at the root of the checkout — a fixed path (the path is
part of what a later run must find again), listed in ``.gitignore``.

The minimum compile time worth caching drops from JAX's default 1 s to
0: many of the VM's programs compile in well under a second (a short
op-group run's executable; a primitive of the per-op dynamic stream),
and at the default each new process would compile them again.

Call :func:`configure_compile_cache` before the process compiles
anything: JAX fixes the cache directory at its first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns the path."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def cache_entries(path: str) -> int:
    """Number of compiled programs in a cache directory.

    One ``<key>-cache`` file per program; a size-capped cache also keeps
    a ``<key>-atime`` file beside each, which is not an entry."""
    return sum(1 for _ in Path(path).glob("*-cache"))
