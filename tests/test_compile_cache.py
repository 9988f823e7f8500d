"""Placement of JAX's persistent compilation cache (``repro.compile_cache``)."""

import os

import jax
import pytest

from repro import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Restore JAX's cache directory after the test changes it."""
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_unset_env_places_cache_in_checkout(monkeypatch, cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_set_env_leaves_cache_to_jax(monkeypatch, cache_config, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir is None


def test_cache_path_is_fixed_and_ignored_by_git():
    assert compile_cache.compile_cache_dir({}) == compile_cache.DEFAULT_DIR
    assert compile_cache.compile_cache_dir({compile_cache.ENV_VAR: "/x"}) \
        is None
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert "/.jax_cache/" in f.read().split()
