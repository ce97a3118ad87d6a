"""Where JAX keeps its persistent compilation cache for this repository.

``enable_compile_cache`` is called by the entry points — ``chip_smoke.py``,
``examples/serve_aggregates.py``, ``benchmarks/run.py`` — before their first
compile, and never when a module is imported, so importing ``repro`` (and
running the tests) leaves JAX's cache configuration alone.

* ``JAX_COMPILATION_CACHE_DIR`` set: that directory is the cache.  JAX reads
  the variable itself; nothing is set in code.
* Unset: the cache is ``<checkout>/.jax_cache`` (git-ignored).  The path is
  fixed — never a temporary name, a process id or a time — because it is
  part of what a later run of the same checkout must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
