"""Where JAX's persistent compilation cache lives.

The command-line entry points (``python -m repro.launch.train``,
``python -m repro.launch.serve``, ``chip_smoke.py``) call
:func:`enable_compile_cache` before their first compile, so a second run
of the same program skips compilation.  ``JAX_COMPILATION_CACHE_DIR``, when
set, names the directory (JAX reads it itself); otherwise the cache is the
fixed directory ``.jax_cache/`` at the checkout root.  The path is part of
the cache key, so it is never built from a temporary name, a pid or the
time.  An installed copy of the package, outside any checkout, has no such
root: unless the variable is set, the cache stays off.  Library code and
tests never turn the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["compile_cache_dir", "enable_compile_cache"]

ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> checkout root
_ROOT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str | None:
    """The cache directory: ``$JAX_COMPILATION_CACHE_DIR``, else
    ``<checkout>/.jax_cache``, else None when the package does not run from
    a checkout (no ``pyproject.toml`` at the would-be root)."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    if (_ROOT / "pyproject.toml").is_file():
        return str(_ROOT / ".jax_cache")
    return None


def enable_compile_cache() -> str | None:
    """Turn the persistent compilation cache on; returns its directory, or
    None when there is none (the cache stays off).

    Sets no directory in code when ``JAX_COMPILATION_CACHE_DIR`` is set.
    Call before the process compiles anything: JAX fixes the cache's
    directory at the first compile.
    """
    path = compile_cache_dir()
    if path and not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
