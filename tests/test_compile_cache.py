"""repro.compile_cache: JAX_COMPILATION_CACHE_DIR wins, else a fixed in-tree
directory that git ignores."""
import os

import jax

from repro import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cache_dir_from_env_else_fixed_in_tree(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.configure() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads the env

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert compile_cache.configure() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == compile_cache.DEFAULT_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
