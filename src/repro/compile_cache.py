"""Where JAX's persistent compilation cache lives.

Call :func:`enable_compile_cache` once, before the first compile, from
an entry point (``chip_smoke.py``, ``benchmarks/run.py``).  Library code
never calls it.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it at import,
  so nothing is set here and the cache lands where the caller said.
* Unset: the cache goes to :data:`DEFAULT_DIR`, a fixed directory in
  the checkout (``.jax_cache``, listed in ``.gitignore``).  The path is
  part of the cache key, so it is never derived from a temp name, a
  pid or the time: a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
