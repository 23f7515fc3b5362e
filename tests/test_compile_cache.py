"""Where the entry points keep JAX's persistent compilation cache.

Only the directory is resolved here: tests never turn the cache on.
"""
import os

from repro.launch import compile_cache


def test_environment_directory_wins(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)


def test_default_is_one_fixed_directory_in_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.compile_cache_dir() == os.path.join(root,
                                                             ".jax_cache")
    assert compile_cache.compile_cache_dir() == \
        compile_cache.DEFAULT_CACHE_DIR
