"""The persistent compilation cache's directory (launch/compile_cache.py):
the env var wins outright; without it, one fixed path in the checkout."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_var_is_the_only_directory(monkeypatch, tmp_path,
                                       restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_unset_uses_one_fixed_path_in_the_checkout(monkeypatch,
                                                   restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    first = compile_cache.enable()
    second = compile_cache.enable()
    assert first == second == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
