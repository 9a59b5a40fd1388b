"""Where the entry points' persistent compilation cache goes."""
import jax

from repro.runtime import compile_cache


def _recorded_updates(monkeypatch):
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.__setitem__(name, value))
    return calls


def test_environment_directory_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = _recorded_updates(monkeypatch)
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in calls
    assert calls["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_fixed_checkout_directory_otherwise(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _recorded_updates(monkeypatch)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.CHECKOUT_CACHE_DIR)
    assert calls["jax_compilation_cache_dir"] == path
    assert (compile_cache.CHECKOUT_CACHE_DIR.parent / "pyproject.toml").is_file()
    assert compile_cache.enable_compile_cache() == path   # same every call
