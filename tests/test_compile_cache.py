"""Where the persistent compilation cache goes (launch/compile_cache.py)."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_wins_and_nothing_is_set(monkeypatch, tmp_path,
                                         cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_the_fixed_repo_dir(monkeypatch, cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.use_compile_cache()
    assert got == str(compile_cache.REPO_CACHE)
    assert compile_cache.REPO_CACHE.name == ".jax_cache"
    assert (compile_cache.REPO_CACHE.parent / "chip_smoke.py").is_file()
    assert jax.config.jax_compilation_cache_dir == got
