"""The entry points' persistent compilation cache location."""
from pathlib import Path

import jax
import pytest

from repro.compile_cache import ENV_VAR, enable_compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_wins_and_nothing_is_set(monkeypatch, tmp_path,
                                         cache_dir_config):
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None


def test_fallback_is_fixed_and_inside_the_checkout(monkeypatch,
                                                   cache_dir_config):
    monkeypatch.delenv(ENV_VAR, raising=False)
    path = enable_compile_cache()
    assert Path(path) == CHECKOUT / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == path
    assert enable_compile_cache() == path
