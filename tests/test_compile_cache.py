"""``launch.compile_cache.enable``: where the persistent compilation cache
goes. The TPU branch is steered here by faking the backend name; the
global JAX config it touches is restored after each test."""
from __future__ import annotations

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_off_the_tpu_nothing_is_set(restore_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() is None  # tests run on the CPU
    assert jax.config.jax_compilation_cache_dir == before


def test_tpu_default_is_the_fixed_checkout_dir(restore_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    path = compile_cache.enable()
    assert path == str(compile_cache.DEFAULT_DIR)
    assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
    assert (compile_cache.DEFAULT_DIR.parent / "chip_smoke.py").exists()
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_tpu_env_dir_wins_and_sets_no_other(restore_config, monkeypatch,
                                           tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    assert compile_cache.enable() == str(tmp_path)
    for k, v in before.items():
        assert getattr(jax.config, k) == v, k
