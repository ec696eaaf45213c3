"""The one persistent-compilation-cache location every entry point uses."""
import os

import jax
import pytest

from repro.launch.compile_cache import DEFAULT_CACHE_DIR, enable_compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_directory_is_used_and_no_other(monkeypatch, tmp_path,
                                                restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_default_is_one_fixed_path_in_the_checkout(monkeypatch,
                                                   restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first, second = enable_compile_cache(), enable_compile_cache()
    assert first == second == DEFAULT_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == DEFAULT_CACHE_DIR
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_cache")
