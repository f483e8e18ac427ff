"""ceph_tpu.utils.jaxenv: where the persistent compile cache lives."""

import os

import jax
import pytest

import ceph_tpu
from ceph_tpu.utils.jaxenv import enable_compile_cache


@pytest.fixture
def cache_config():
    """Whatever a test sets, the worker's later tests run uncached."""
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    was = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in was.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def test_env_dir_wins_and_nothing_is_set_in_code(monkeypatch,
                                                 cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
    was = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == "/some/where"
    assert jax.config.jax_compilation_cache_dir == was
    # small programs are kept in this mode too
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_fixed_path_inside_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(
        os.path.abspath(ceph_tpu.__file__)))
    path = enable_compile_cache()
    assert path == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert enable_compile_cache() == path       # no pid, no time in it


def test_unwritable_path_runs_uncached(monkeypatch, cache_config):
    """Installed outside a checkout the fixed path cannot be made: the
    process goes on without a cache instead of failing at start-up."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)

    def refuse(path, exist_ok=False):
        raise PermissionError(path)

    monkeypatch.setattr(os, "makedirs", refuse)
    was = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == was
