"""The persistent compile cache follows ``JAX_COMPILATION_CACHE_DIR`` when it
is set, and otherwise lives at one fixed place inside the checkout."""
from pathlib import Path

import jax
import pytest

from repro import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def keep_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_env_dir_is_used_and_left_alone(monkeypatch, tmp_path, keep_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_in_checkout(monkeypatch, keep_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(REPO / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert compile_cache.enable_compile_cache() == want
