"""Where the persistent compilation cache goes (``repro.runtime.compile_cache``)."""

import os
import subprocess
import sys

import jax
import pytest

from repro.runtime import compile_cache


@pytest.fixture
def cache_config():
    """Restore the process's cache directory after the test."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_wins_and_nothing_else_is_set(monkeypatch, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_one_fixed_dir_in_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(checkout, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert os.path.isdir(want)
    # a second call (another entry point, a second process) lands there too
    assert compile_cache.enable_compile_cache() == want


def test_importing_the_entry_points_sets_no_cache():
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.abspath("src")
    script = (
        "import jax, repro.serve.__main__, repro.obs.__main__\n"
        "assert jax.config.jax_compilation_cache_dir is None, "
        "jax.config.jax_compilation_cache_dir\n"
        "print('OK')\n"
    )
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr
