"""The persistent compilation cache helper: JAX's own variable wins, and
without it the cache sits at one fixed path inside the checkout."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_variable_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # untouched


def test_default_is_fixed_in_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    path = compile_cache.setup_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.setup_compile_cache() == path  # no temp, pid, time
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
