"""repro.launch.compile_cache: ``$JAX_COMPILATION_CACHE_DIR`` places the
persistent cache when it is set (the helper then sets no directory);
unset, the cache goes to one fixed directory inside the checkout."""

from pathlib import Path

import jax

from repro.launch import compile_cache


def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda name, value: calls.append((name, value)))
    return calls


def test_env_var_leaves_placement_to_jax(monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
    calls = _record_updates(monkeypatch)
    compile_cache.enable()
    assert calls == []


def test_unset_env_uses_the_checkout_directory(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    calls = _record_updates(monkeypatch)
    compile_cache.enable()
    repo = Path(__file__).resolve().parents[1]
    assert calls == [("jax_compilation_cache_dir", str(repo / ".jax_cache"))]
