"""The entry points' persistent compilation cache: one fixed directory."""
import jax

from repro.runtime.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache


def test_cache_follows_the_environment_and_sets_nothing_else(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_defaults_to_a_fixed_directory_in_the_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        assert enable_compile_cache() == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
        assert enable_compile_cache() == str(CHECKOUT_CACHE_DIR)  # stable
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert CHECKOUT_CACHE_DIR.name == ".jax_cache"
    assert (CHECKOUT_CACHE_DIR.parent / "pyproject.toml").exists()
