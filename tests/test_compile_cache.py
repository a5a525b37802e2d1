"""Where the entry points keep jax's persistent compilation cache."""

from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache


@pytest.fixture
def cache_dir_config():
    """Restore the process-wide cache directory after the test."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_follows_environment(monkeypatch, cache_dir_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/cache")
    assert enable_compile_cache() == "/some/cache"
    assert jax.config.jax_compilation_cache_dir == before   # set nothing


def test_cache_defaults_to_fixed_checkout_dir(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = Path(__file__).resolve().parents[1]
    assert CHECKOUT_CACHE_DIR == root / ".jax_cache"
    assert enable_compile_cache() == str(root / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(root / ".jax_cache")
    assert ".jax_cache/" in (root / ".gitignore").read_text().splitlines()
