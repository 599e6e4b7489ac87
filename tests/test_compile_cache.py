"""The persistent compilation cache helper (``launch/compile_cache.py``):
``JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set in code;
without it the cache lives at one fixed path inside the checkout."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_is_the_cache_dir(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # set nothing


def test_default_is_one_fixed_path_in_the_checkout(monkeypatch,
                                                   restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = enable_compile_cache()
    assert first == str(REPO / ".jax_cache") == str(REPO_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == first
    assert enable_compile_cache() == first


def test_gitignore_lists_the_default_cache():
    lines = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in lines


def test_compiles_land_in_the_env_dir_only(tmp_path):
    """A process with the variable set writes its compiled programs
    there, and nothing into the checkout's default directory."""
    cache = tmp_path / "cache"
    script = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()
    """)
    default = REPO_CACHE_DIR
    before = sorted(os.listdir(default)) if default.exists() else None
    env = {**os.environ, "PYTHONPATH": str(REPO / "src"),
           "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(cache)}
    proc = subprocess.run([sys.executable, "-c", script], cwd=tmp_path,
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert any(cache.iterdir())
    after = sorted(os.listdir(default)) if default.exists() else None
    assert after == before
