"""The persistent compile cache's one rule: JAX_COMPILATION_CACHE_DIR if
set (left to JAX), else the fixed <checkout>/.jax_cache."""
import jax
import pytest

from repro import compile_cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_is_left_to_jax(monkeypatch, restore_cache_dir, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set in code


def test_unset_env_uses_the_fixed_checkout_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.use_compile_cache()
    assert got == str(compile_cache.CHECKOUT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert compile_cache.use_compile_cache() == got  # the same path every run
