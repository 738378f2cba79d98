"""The persistent compilation cache lives at one fixed path."""
import jax

from repro import compile_cache


def test_env_dir_is_left_to_jax(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert calls == []


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.CACHE_DIR)
    assert compile_cache.CACHE_DIR.name == ".jax_cache"
    assert (compile_cache.CACHE_DIR.parent / "src" / "repro").is_dir()
    assert calls == [("jax_compilation_cache_dir", path)]
