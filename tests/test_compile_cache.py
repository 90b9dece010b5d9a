"""The persistent compile cache is placed from outside first
(utils/compile_cache.py): JAX_COMPILATION_CACHE_DIR when set, else the
checkout's own .jax_cache."""

import os

import jax
import pytest

from cuvite_tpu.utils.compile_cache import enable_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_left_to_jax(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    monkeypatch.delenv("CUVITE_NO_COMPILE_CACHE", raising=False)
    jax.config.update("jax_compilation_cache_dir", "/sentinel")
    enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == "/sentinel"


def test_default_dir_is_the_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("CUVITE_NO_COMPILE_CACHE", raising=False)
    jax.config.update("jax_compilation_cache_dir", "/sentinel")
    enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        REPO, ".jax_cache")


def test_opt_out_sets_nothing(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("CUVITE_NO_COMPILE_CACHE", "1")
    jax.config.update("jax_compilation_cache_dir", "/sentinel")
    enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == "/sentinel"
