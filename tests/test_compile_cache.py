"""The compile-cache helper the chip entry points call at start-up."""
from __future__ import annotations

from pathlib import Path

import jax

import repro
from repro.launch import compile_cache

CHECKOUT = Path(repro.__file__).resolve().parents[2]


def _record_updates(monkeypatch) -> list:
    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: calls.append(a))
    return calls


def test_env_dir_wins_and_nothing_is_set(monkeypatch, tmp_path):
    calls = _record_updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch):
    calls = _record_updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    assert Path(first) == CHECKOUT / ".jax_cache"
    assert compile_cache.enable_compile_cache() == first
    assert calls == [("jax_compilation_cache_dir", first)] * 2


def test_import_leaves_the_cache_off():
    assert jax.config.jax_compilation_cache_dir != str(
        compile_cache.REPO_CACHE_DIR)
