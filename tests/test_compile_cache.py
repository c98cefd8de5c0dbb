"""The persistent compile cache is placed by the entry points, never by
importing the library (so the tests write no cache)."""
import os

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

import repro
from repro import compile_cache

_KEYS = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def restore_config():
    before = {k: getattr(jax.config, k) for k in _KEYS}
    yield before
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_import_sets_no_cache():
    assert repro is not None
    # JAX itself reads the variable; importing repro adds nothing to it
    assert jax.config.jax_compilation_cache_dir == os.environ.get(
        "JAX_COMPILATION_CACHE_DIR")


def test_env_dir_is_used_and_nothing_is_set(monkeypatch, tmp_path, restore_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert {k: getattr(jax.config, k) for k in _KEYS} == restore_config


def test_default_dir_is_the_ignored_checkout_path(monkeypatch, restore_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable()
    assert path == str(compile_cache.DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    root = compile_cache.DEFAULT_DIR.parent
    ignored = (root / ".gitignore").read_text().split()
    assert f"{compile_cache.DEFAULT_DIR.name}/" in ignored
