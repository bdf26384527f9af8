"""Where the entry points put JAX's persistent compilation cache."""
import jax
import pytest

from repro.utils.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache


@pytest.mark.parametrize("env_dir", [None, "/cache/from/env"])
def test_compile_cache_location(monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without
    it the cache goes to the fixed in-checkout path."""
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        where = enable_compile_cache()
        if env_dir is None:
            assert where == str(CHECKOUT_CACHE_DIR)
            assert jax.config.jax_compilation_cache_dir == where
            assert CHECKOUT_CACHE_DIR.parent.joinpath("chip_smoke.py").exists()
        else:
            assert where == env_dir
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
