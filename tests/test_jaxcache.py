"""Placement of JAX's persistent compilation cache (utils/jaxcache.py)."""
import os

import jax
import pytest

from ekuiper_tpu.utils import jaxcache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def config_writes(monkeypatch):
    """Record what setup() writes into jax.config, and restore it."""
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    writes = {}
    real = jax.config.update

    def update(name, value):
        writes[name] = value
        real(name, value)

    monkeypatch.setattr(jax.config, "update", update)
    yield writes
    real("jax_compilation_cache_dir", prev_dir)
    real("jax_persistent_cache_min_compile_time_secs", prev_min)


def test_variable_set_leaves_jax_directory_alone(config_writes, monkeypatch):
    """JAX reads JAX_COMPILATION_CACHE_DIR itself; code writes nothing
    over it."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/by/operator")
    before = jax.config.jax_compilation_cache_dir
    assert jaxcache.setup() == "/placed/by/operator"
    assert "jax_compilation_cache_dir" not in config_writes
    assert jax.config.jax_compilation_cache_dir == before


def test_variable_unset_uses_fixed_path_in_checkout(config_writes,
                                                    monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")  # no pid, time or temp name
    assert jaxcache.setup() == want == jaxcache.DEFAULT_DIR
    assert jax.config.jax_compilation_cache_dir == want
    # sub-second boundary programs are cached too
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
