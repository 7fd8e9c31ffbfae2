"""Where entry points keep JAX's persistent compilation cache."""
from pathlib import Path

import jax
import pytest

from repro.compile_cache import ENV_VAR, use_persistent_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_wins(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    assert use_persistent_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_default_is_fixed_repo_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(ENV_VAR, raising=False)
    path = use_persistent_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert use_persistent_cache() == path        # same path every call
