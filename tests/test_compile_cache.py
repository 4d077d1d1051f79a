"""The persistent compile cache helper: the deployment's directory verbatim,
else one fixed directory inside the checkout — never a moving path."""

from pathlib import Path

import jax
import pytest

from repro.runtime import compile_cache


@pytest.fixture
def restore_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_env_dir_used_verbatim(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/srv/shared/xla-cache/")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/srv/shared/xla-cache/"
    # JAX reads the variable itself: the helper sets nothing on top of it
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_env_uses_fixed_checkout_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    root = Path(__file__).resolve().parent.parent
    assert Path(path) == root / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == path


def test_second_call_same_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    assert compile_cache.enable_compile_cache() == first
    assert jax.config.jax_compilation_cache_dir == first
