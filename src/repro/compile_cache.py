"""Process setup for JAX's persistent compilation cache.

Entry points call ``use_persistent_cache()`` before their first compile.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, the cache lives there and
nowhere else; otherwise it lives at ``<repo>/.jax_cache``.  The path is
fixed, never temporary, per-process or time-stamped: a later process
finds what this one wrote only by looking in the same directory.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_persistent_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.  Takes effect only if called before the
    process's first compile (JAX decides once whether the cache is on)."""
    import jax
    path = os.environ.get(ENV_VAR) or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
