"""Placement of JAX's persistent compilation cache
(``repro.compile_cache``): where ``JAX_COMPILATION_CACHE_DIR`` says,
else one fixed directory in the checkout."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro import compile_cache

REPO = Path(__file__).resolve().parents[1]


def test_env_dir_receives_the_cache_files(tmp_path):
    cache = tmp_path / "cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=str(REPO / "src"))
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.compile_cache import enable_compile_cache\n"
            f"assert enable_compile_cache() == {str(cache)!r}\n"
            "jax.jit(lambda x: x * 3 + 1)(jnp.ones(8)).block_until_ready()\n")
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)
    assert any(cache.iterdir())


def test_unset_env_uses_the_fixed_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
