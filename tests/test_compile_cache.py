"""Where entry points put JAX's persistent compilation cache."""
import jax

from repro import compile_cache


def test_environment_cache_dir_wins_and_nothing_is_set(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX owns the placement: the
    helper reports that directory and touches no JAX setting."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_checkout_cache_dir_is_fixed_and_ignored():
    """The fallback is one fixed path inside the checkout, git-ignored."""
    root = compile_cache.CHECKOUT_CACHE_DIR.parent
    assert compile_cache.CHECKOUT_CACHE_DIR.name == ".jax_cache"
    assert (root / "src" / "repro" / "compile_cache.py").exists()
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
