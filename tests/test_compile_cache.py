"""The entry points' persistent compile cache: JAX_COMPILATION_CACHE_DIR wins
and nothing else is set; otherwise one fixed directory at the repo root."""
import os
import subprocess
import sys

import jax

from repro.launch import compile_cache

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_env_dir_is_used_and_nothing_is_set(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_the_fixed_repo_dir(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.enable_compile_cache()
        assert got == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_launcher_fills_the_env_dir(tmp_path):
    cache = tmp_path / "cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "pod", "--arch", "yi-6b",
         "--smoke", "--steps", "1", "--seq", "16"],
        capture_output=True, text=True, timeout=300, env=env, cwd=tmp_path)
    assert r.returncode == 0, r.stderr[-2000:]
    assert os.listdir(cache)
