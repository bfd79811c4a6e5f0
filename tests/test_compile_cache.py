"""Where `enable_compile_cache` puts the persistent compilation cache."""

from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import CHECKOUT_CACHE, enable_compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture()
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env_dir", ["/srv/jax-cache", None])
def test_cache_dir(cache_dir_config, monkeypatch, env_dir):
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    got = enable_compile_cache()
    if env_dir:
        # JAX reads the variable itself; nothing is set in code
        assert got == env_dir
        assert jax.config.jax_compilation_cache_dir == was
    else:
        assert got == CHECKOUT_CACHE == jax.config.jax_compilation_cache_dir
        cache = Path(got)
        assert cache.parent == REPO
        assert f"{cache.name}/" in (REPO / ".gitignore").read_text().split()
