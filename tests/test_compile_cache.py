"""The persistent compilation cache lives at one fixed path."""
import pathlib

from repro.common import compile_cache


def test_env_names_the_cache_dir(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)


def test_default_is_fixed_inside_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first, second = compile_cache.cache_dir(), compile_cache.cache_dir()
    assert first == second
    root = pathlib.Path(__file__).resolve().parents[1]
    assert pathlib.Path(first) == root / ".jax_cache"
