"""Where the persistent compilation cache goes: ``JAX_COMPILATION_CACHE_DIR``
when the environment sets it, otherwise a fixed ``.jax_cache`` at the root
of the checkout — never a directory that moves between runs, since the path
is part of the cache key."""

import pathlib

import jax
import pytest

from repro.core import fused

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def jax_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_cache_follows_environment(monkeypatch, tmp_path, jax_cache_config):
    target = tmp_path / "cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(target))
    assert fused.enable_persistent_cache() == str(target)
    assert jax.config.jax_compilation_cache_dir == str(target)
    assert target.is_dir()


def test_cache_defaults_to_checkout(monkeypatch, jax_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = REPO_ROOT / ".jax_cache"
    assert fused.DEFAULT_CACHE_DIR == want
    assert fused.enable_persistent_cache() == str(want)
    assert jax.config.jax_compilation_cache_dir == str(want)
