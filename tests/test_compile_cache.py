"""The persistent compile cache: the environment's directory, else the
repo's fixed ``.jax-cache``."""
import pathlib

import jax
import pytest

from repro import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir():
    """Restore JAX's cache directory after the test (nothing compiles in
    between, so the cache itself is never opened)."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_follows_environment(cache_dir, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # set nothing


def test_repo_cache_otherwise(cache_dir, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.enable() == str(REPO / ".jax-cache")
    assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax-cache")
