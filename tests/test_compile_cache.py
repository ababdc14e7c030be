"""``petastorm_tpu.jax.compile_cache``: the cache's place comes from the
environment when it is set there, and is a fixed path in the checkout when
it is not."""
import pathlib

ROOT = pathlib.Path(__file__).parent.parent


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    import jax

    from petastorm_tpu.jax import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.ensure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set in code


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(monkeypatch):
    import jax

    from petastorm_tpu.jax import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = compile_cache.ensure_compile_cache()
        assert first == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        assert compile_cache.ensure_compile_cache() == first   # twice the same
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
