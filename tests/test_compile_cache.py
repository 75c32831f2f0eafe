"""Where the entry points place JAX's persistent compilation cache. The
config update is captured, never applied: tests never turn the cache on."""
import jax

from repro.launch import compile_cache


def _updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_dir_stands(monkeypatch, tmp_path):
    """JAX reads JAX_COMPILATION_CACHE_DIR itself; no code sets another."""
    calls = _updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert calls == []


def test_unset_env_uses_fixed_checkout_dir(monkeypatch):
    calls = _updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.use_compile_cache()
    repo = compile_cache.DEFAULT_DIR.parent
    assert got == str(repo / ".jax_cache")
    assert (repo / "src" / "repro").is_dir()          # the checkout root
    assert calls == [("jax_compilation_cache_dir", got)]
    assert compile_cache.use_compile_cache() == got   # same path every call
