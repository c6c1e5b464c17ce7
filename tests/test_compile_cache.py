"""The entry points' persistent compilation cache helper."""
import jax

from repro.launch import compile_cache


def _restoring(fn):
    """Run ``fn`` and put the process-wide cache directory back."""
    before = jax.config.jax_compilation_cache_dir
    try:
        return fn()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir

    def call():
        assert compile_cache.enable_compile_cache() is None
        return jax.config.jax_compilation_cache_dir

    assert _restoring(call) == before


def test_fixed_dir_inside_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)

    def call():
        first = compile_cache.enable_compile_cache()
        second = compile_cache.enable_compile_cache()
        return first, second, jax.config.jax_compilation_cache_dir

    first, second, configured = _restoring(call)
    assert first == second == compile_cache.CACHE_DIR
    assert configured == str(compile_cache.CACHE_DIR)
    root = compile_cache.CACHE_DIR.parent
    assert (root / "chip_smoke.py").is_file()
    assert ".jax_cache/" in (root / ".gitignore").read_text().splitlines()
