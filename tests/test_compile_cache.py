"""The one place that decides where JAX's persistent compile cache lives."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_wins_and_enable_sets_no_other(restore_cache_dir, tmp_path):
    env = {compile_cache.ENV_VAR: str(tmp_path)}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert compile_cache.enable(env) == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_enable_without_env_points_jax_at_the_checkout(restore_cache_dir):
    want = str(CHECKOUT / ".jax_cache")
    assert compile_cache.enable({}) == want
    assert jax.config.jax_compilation_cache_dir == want
    # the same path every call: no temp name, pid or time in it
    assert compile_cache.enable({}) == want
    assert ".jax_cache/" in (CHECKOUT / ".gitignore").read_text().split()
