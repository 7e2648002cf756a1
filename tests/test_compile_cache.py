"""The persistent compilation cache is placed from outside: where
``JAX_COMPILATION_CACHE_DIR`` says, else ``.jax_cache/`` in the
checkout — never a temporary or per-process path."""
import os
import subprocess
import sys

import jax

from repro.launch import compile_cache

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_default_dir_is_fixed_in_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.use_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_is_used_and_nothing_else_is_set(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    prev = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == prev


def test_serve_cli_writes_entries_where_the_env_says(tmp_path):
    """A served program compiled through the CLI lands in the directory
    the environment names (the entry point calls the helper)."""
    cache = tmp_path / "cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(ROOT, "src"),
                    os.environ.get("PYTHONPATH", "")]))
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch",
         "mobilenet_v1", "--mode", "latency", "--requests", "1",
         "--stages", "2", "--image-size", "32"],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert cache.is_dir() and any(cache.iterdir())
    assert not (tmp_path / ".jax_cache").exists()
