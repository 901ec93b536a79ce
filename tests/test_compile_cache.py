"""The entry points' persistent compilation cache location."""
import pathlib

import jax
import pytest

from repro.launch import cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_var_is_honoured(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_default_is_fixed_inside_the_checkout(monkeypatch,
                                              restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = pathlib.Path(__file__).resolve().parents[1]
    want = str(repo / ".jax_cache")
    assert cache.enable_compile_cache() == want
    assert cache.enable_compile_cache() == want          # same every call
    assert jax.config.jax_compilation_cache_dir == want
    assert ".jax_cache/" in (repo / ".gitignore").read_text().splitlines()
