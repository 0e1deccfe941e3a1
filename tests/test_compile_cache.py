"""The persistent compile cache goes where the entry points put it."""
import pathlib

import jax

from repro import compile_cache

REPO = pathlib.Path(__file__).resolve().parent.parent


def test_env_var_wins_and_no_other_dir_is_set(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/cache/from/env")
    assert compile_cache.enable_compile_cache() == "/cache/from/env"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_one_fixed_ignored_dir_in_the_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    try:
        path = compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert path == str(REPO / ".jax_cache")
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()
