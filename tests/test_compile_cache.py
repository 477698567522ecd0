"""The entry points' persistent compilation cache location."""

import os

import jax
import pytest

from conftest import REPO


@pytest.fixture
def cache_dir_config():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_env_var_wins_and_nothing_else_is_set(monkeypatch, cache_dir_config):
    from repro.launch.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_repo_dir_ignored_by_git(monkeypatch,
                                                  cache_dir_config):
    from repro.launch.compile_cache import CACHE_DIR, enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert CACHE_DIR == os.path.join(REPO, ".jax_cache")
    assert enable_compile_cache() == CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == CACHE_DIR
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
