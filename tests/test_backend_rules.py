"""Backend rules of the chip path, checked on the CPU: the persistent
compile cache lands where `JAX_COMPILATION_CACHE_DIR` says, else at one
fixed path.
"""

from __future__ import annotations

import os

import pytest

import jax

from repro.core import pipeline as PL


@pytest.fixture
def cache_config():
    """Restore JAX's compile-cache directory after the test."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_compile_cache_honours_env(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv(PL.COMPILE_CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert PL.use_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; no other directory is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_path(monkeypatch, cache_config):
    monkeypatch.delenv(PL.COMPILE_CACHE_ENV, raising=False)
    first, second = PL.use_compile_cache(), PL.use_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert first == second == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
