"""Where ``repro.compile_cache`` puts JAX's persistent compilation cache."""
import os

import jax
import pytest

from repro import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_default_dir_is_fixed_and_gitignored(monkeypatch, restore_cache_config):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == compile_cache.DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_env_dir_wins_and_no_other_path_is_set(monkeypatch, restore_cache_config,
                                               tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper leaves the setting alone
    assert jax.config.jax_compilation_cache_dir == before
