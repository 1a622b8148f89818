"""core.compile_cache.enable(): JAX_COMPILATION_CACHE_DIR, when set, is
left to JAX; otherwise the cache sits at the fixed <repo>/.jax_cache.
``jax.config.update`` is replaced by a recorder, so the test process's
own JAX config never changes."""

import os

import jax
import pytest

from raft_tpu.core import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def updates(monkeypatch):
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.__setitem__(name, value))
    monkeypatch.setattr(compile_cache, "_enabled", False)
    return calls


def test_env_dir_left_to_jax(updates, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable()
    assert "jax_compilation_cache_dir" not in updates


def test_default_dir_is_repo_jax_cache(updates, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.enable()
    assert updates["jax_compilation_cache_dir"] == os.path.join(
        REPO, ".jax_cache")
