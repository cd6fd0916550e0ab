"""The persistent-compilation-cache helper called by the entry points."""
import os
import tempfile

import jax
import pytest

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_wins_and_nothing_is_set(monkeypatch, cache_dir_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/some/shared/cache")
    assert compile_cache.enable() == "/some/shared/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_fallback_is_fixed_and_inside_the_checkout(monkeypatch,
                                                   cache_dir_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable()
    assert path == compile_cache.enable()
    assert jax.config.jax_compilation_cache_dir == path
    assert os.path.commonpath([path, REPO]) == REPO
    assert not path.startswith(tempfile.gettempdir())
    with open(os.path.join(REPO, ".gitignore")) as f:
        ignored = f.read().split()
    assert os.path.relpath(path, REPO) + "/" in ignored
