"""Where the entry points keep JAX's persistent compilation cache.

A cold process compiles every tick program again; the persistent cache lets
the next process on the same machine load them instead.  The directory is
``JAX_COMPILATION_CACHE_DIR`` where that is set (JAX reads the variable
itself, and nothing else is configured), and otherwise the fixed
``.jax_cache/`` at the repo root (listed in ``.gitignore``), so that every
run of a checkout finds what the last one compiled.  Nothing is configured
at import time: entry points call :func:`use_compile_cache` before their
first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_DIR", "use_compile_cache"]

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
