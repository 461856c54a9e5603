"""Where the persistent compilation cache goes, and the compile clock."""

import jax
import jax.numpy as jnp
import pytest

from repro.core import compile_cache as cc

_KEYS = (
    "jax_compilation_cache_dir",
    "jax_persistent_cache_min_compile_time_secs",
    "jax_persistent_cache_min_entry_size_bytes",
)


@pytest.fixture
def restore_config():
    saved = {k: getattr(jax.config, k) for k in _KEYS}
    try:
        yield
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


def test_cache_dir_from_the_environment_is_left_alone(monkeypatch,
                                                      restore_config):
    monkeypatch.setenv(cc.CACHE_VAR, "/elsewhere/cache")
    jax.config.update("jax_compilation_cache_dir", "/elsewhere/cache")
    assert cc.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == "/elsewhere/cache"


def test_cache_dir_defaults_to_a_fixed_path_in_the_checkout(monkeypatch,
                                                            restore_config):
    monkeypatch.delenv(cc.CACHE_VAR, raising=False)
    path = cc.enable_compile_cache()
    assert path == str(cc.DEFAULT_CACHE_DIR) == jax.config.jax_compilation_cache_dir
    assert cc.DEFAULT_CACHE_DIR.parent.joinpath("chip_smoke.py").is_file()
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_compile_clock_counts_backend_compiles():
    clock = cc.CompileClock()
    jax.jit(lambda x: x * 3 + 1).lower(jnp.ones(7)).compile()
    assert clock.seconds > 0
