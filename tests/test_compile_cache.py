"""Where entry points put JAX's persistent compilation cache."""
import os

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_environment_directory_wins(monkeypatch, tmp_path, restore_cache_dir):
    """With JAX_COMPILATION_CACHE_DIR set, that is the cache and the code
    sets no other directory."""
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_directory_is_fixed_under_checkout(monkeypatch,
                                                   restore_cache_dir):
    """Unset, the cache lives at <checkout>/.cache/jax_compile — a fixed
    path (it is part of the cache key), apart from the RD tables."""
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    checkout = os.path.abspath(os.path.join(
        os.path.dirname(__file__), ".."))
    want = os.path.join(checkout, ".cache", "jax_compile")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert os.path.isdir(want)
