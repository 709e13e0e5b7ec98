"""Placement of jax's persistent compilation cache (repro.compile_cache):
``JAX_COMPILATION_CACHE_DIR`` wins and nothing is set in code; otherwise a
fixed directory inside the checkout that git ignores."""

import os
import subprocess
import sys
from pathlib import Path

import jax

from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = """
from repro.compile_cache import use_compile_cache
use_compile_cache()
import jax, jax.numpy as jnp
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
print(jax.config.jax_compilation_cache_dir)
"""


def test_fallback_is_fixed_and_ignored():
    assert compile_cache.CACHE_DIR == ROOT / ".jax_cache"
    ignored = (ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


def test_env_var_set_means_nothing_set_in_code(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", "unchanged")
    try:
        compile_cache.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == "unchanged"
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_unset_uses_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        compile_cache.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(compile_cache.CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_run_writes_entries_where_env_var_says(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split()[-1] == str(tmp_path)
    assert any(p.name.startswith("jit_") for p in tmp_path.iterdir())
