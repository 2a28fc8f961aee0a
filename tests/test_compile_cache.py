"""``repro.launch.compile_cache``: the persistent cache directory is
placed from outside, else fixed inside the checkout."""
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


def test_environment_directory_is_used_as_is(monkeypatch, tmp_path,
                                             restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself: nothing else is set
    assert jax.config.jax_compilation_cache_dir == restore_cache_dir


def test_default_is_fixed_directory_in_checkout(monkeypatch,
                                                restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert enable_compile_cache() == path       # same on every call
