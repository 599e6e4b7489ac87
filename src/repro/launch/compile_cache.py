"""JAX's persistent compilation cache, set up once per entry point.

Every entry point (``launch/serve.py``, ``launch/train.py``,
``chip_smoke.py``) calls ``enable_compile_cache()`` before its first
jit.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and this sets nothing; otherwise the cache lives at one fixed path in
the checkout, ``<repo>/.jax_cache`` (listed in ``.gitignore``) — a
fixed path, because the directory is part of what a cache hit needs.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
