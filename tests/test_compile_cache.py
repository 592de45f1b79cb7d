"""The persistent compile cache lands in one fixed directory."""

import os

import jax
import pytest

from repro.compile_cache import enable_compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_wins(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache(str(tmp_path)) == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_default_is_checkout_dir(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(str(tmp_path), ".jax_cache")
    assert enable_compile_cache(str(tmp_path)) == want
    assert jax.config.jax_compilation_cache_dir == want
    # the same checkout always maps to the same directory
    assert enable_compile_cache(str(tmp_path)) == want
