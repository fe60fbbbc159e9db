"""The persistent compile cache goes where the caller puts it."""

import os
import subprocess
import sys
from pathlib import Path

import jax
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_env_dir_is_used_and_nothing_else(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, entries land there only."""
    code = ("import jax\n"
            "from repro.launch import compile_cache\n"
            "print(compile_cache.enable())\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
            "jax.jit(lambda x: x * 3 + 1)(2.0).block_until_ready()\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    before = (set(compile_cache.DEFAULT_DIR.iterdir())
              if compile_cache.DEFAULT_DIR.exists() else set())
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True, timeout=120)
    assert out.stdout.strip().splitlines()[-1] == str(tmp_path)
    assert any(tmp_path.iterdir())
    after = (set(compile_cache.DEFAULT_DIR.iterdir())
             if compile_cache.DEFAULT_DIR.exists() else set())
    assert after == before


def test_default_dir_is_fixed_and_ignored(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
