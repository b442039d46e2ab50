"""Where the persistent compilation cache goes (launch/compile_cache.py)."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    was_meta = jax.config.jax_compilation_cache_include_metadata_in_key
    was_re = jax.config.jax_hlo_source_file_canonicalization_regex
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      was_meta)
    jax.config.update("jax_hlo_source_file_canonicalization_regex", was_re)


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


@pytest.mark.parametrize("env", [True, False])
def test_keys_hold_the_programs_metadata(monkeypatch, tmp_path, env,
                                         cache_dir_restored):
    """A program that differs from a cached one only in its named scopes
    must not load the cached executable, or a profile shows old names."""
    if env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    compile_cache.use_compile_cache()
    assert jax.config.jax_compilation_cache_include_metadata_in_key
    # the metadata names the program's files relative to the checkout, so
    # a second checkout of the same tree finds the first one's entries
    from repro.core import topology
    text = jax.jit(topology.directed_exponential, static_argnums=0).lower(
        4, 1).as_text(debug_info=True)
    assert "src/repro/core/topology.py" in text
    assert str(compile_cache.ROOT) not in text
