"""The entry points' compile-cache placement (launch/compile_cache.py):
``$JAX_COMPILATION_CACHE_DIR`` when set, else one fixed directory inside
the checkout.  ``jax.config.update`` is intercepted, so no test turns the
persistent cache on."""

import jax

from repro.launch import compile_cache


def _updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_dir_is_used_and_nothing_else_set(monkeypatch, tmp_path):
    calls = _updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert calls == []


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch):
    calls = _updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.use_compile_cache()
    assert first == compile_cache.use_compile_cache()
    assert first == str(compile_cache.CHECKOUT / ".jax_cache")
    assert (compile_cache.CHECKOUT / "chip_smoke.py").exists()
    assert calls == [("jax_compilation_cache_dir", first)] * 2
