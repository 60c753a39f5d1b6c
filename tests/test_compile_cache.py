"""The persistent compile cache helper: where the cache goes."""
from pathlib import Path

import jax
import pytest

from repro.compile_cache import CACHE_ENV, enable_compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture
def jax_cache_config():
    """Restore the cache directory the helper may set."""
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


def test_env_dir_is_used_and_nothing_set(monkeypatch, tmp_path,
                                         jax_cache_config):
    monkeypatch.setenv(CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_dir_in_checkout(monkeypatch, jax_cache_config):
    monkeypatch.delenv(CACHE_ENV, raising=False)
    path = enable_compile_cache()
    assert path == str(CHECKOUT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert enable_compile_cache() == path       # the same on every call
