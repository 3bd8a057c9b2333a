"""The persistent compile cache lives at one fixed, predictable path."""
from __future__ import annotations

from pathlib import Path

import jax

from repro import compile_cache
from repro.compile_cache import compile_cache_dir, enable_compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


def test_env_var_wins(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache_dir() == str(tmp_path)


def test_default_is_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first, second = compile_cache_dir(), compile_cache_dir()
    assert first == second == str(CHECKOUT / ".jax_cache")


def test_enable_sets_jax_config(monkeypatch, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
