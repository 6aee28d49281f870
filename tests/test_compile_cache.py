"""The entry points' persistent compilation cache location."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_config():
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_enable_compilation_cache)
    yield
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_enable_compilation_cache", saved[1])


def test_env_var_wins_and_nothing_else_is_set(monkeypatch, restore_cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/some/shared/cache")
    assert compile_cache.enable() == "/some/shared/cache"
    assert jax.config.jax_compilation_cache_dir == before
    assert jax.config.jax_enable_compilation_cache


def test_default_is_fixed_in_checkout_dir(monkeypatch, restore_cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable()
    assert path == str(compile_cache.DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == path
    # the checkout root: the directory holding src/ and the .gitignore
    assert (compile_cache.DEFAULT_DIR.parent / "src" / "repro").is_dir()
    assert compile_cache.enable() == path   # stable across calls
