"""Backend-dependent defaults (``repro.utils.backend``)."""
import os

import jax
import pytest

from repro.utils.backend import pallas_interpret, use_compile_cache


def test_pallas_interprets_only_on_cpu():
    assert pallas_interpret() == (jax.default_backend() == "cpu")


@pytest.fixture
def cache_dir_config():
    """Restore JAX's compile-cache directory after the test."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_checkout(monkeypatch, tmp_path,
                                            cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(str(tmp_path), ".jax_cache")
    assert use_compile_cache(str(tmp_path)) == want
    assert jax.config.jax_compilation_cache_dir == want
    # the same checkout always maps to the same directory
    assert use_compile_cache(str(tmp_path / "sub" / "..")) == want


def test_compile_cache_env_var_wins(monkeypatch, tmp_path, cache_dir_config):
    env = str(tmp_path / "outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache(str(tmp_path)) == env
    assert jax.config.jax_compilation_cache_dir == before
