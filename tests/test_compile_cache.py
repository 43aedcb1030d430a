"""Where the persistent compile cache goes: $JAX_COMPILATION_CACHE_DIR
when set (and nothing configured in code), else <checkout>/.jax_cache."""
import os

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_used_and_nothing_is_set(monkeypatch, cache_config,
                                            tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_in_the_checkout_and_ignored(monkeypatch,
                                                          cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split(), ".jax_cache is not ignored"
