"""Placement of the persistent compilation cache by the entry points'
helper: the environment variable wins, otherwise a fixed directory
inside the checkout.  ``jax.config.update`` is intercepted, so the test
process itself never turns the cache on."""
import pathlib

import jax

from repro.launch import compile_cache


def _capture(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_dir_is_left_alone(monkeypatch, tmp_path):
    calls = _capture(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch):
    calls = _capture(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache()
    root = pathlib.Path(__file__).resolve().parents[1]
    assert pathlib.Path(first) == root / ".jax_cache"
    assert calls == [("jax_compilation_cache_dir", first)] * 2
