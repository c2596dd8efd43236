"""The persistent compilation cache lives at one fixed directory."""
import os

import jax

from repro.runtime import compile_cache


def _restoring(fn):
    before = jax.config.jax_compilation_cache_dir
    try:
        return fn()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_checkout_directory_when_unset(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = _restoring(lambda: (compile_cache.use_compile_cache(),
                               jax.config.jax_compilation_cache_dir))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == (os.path.join(repo, ".jax_cache"),) * 2
    # the same directory on every call: no temp name, PID or time in it
    assert _restoring(compile_cache.use_compile_cache) == path[0]


def test_environment_directory_is_honoured(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    got = _restoring(lambda: (compile_cache.use_compile_cache(),
                              jax.config.jax_compilation_cache_dir))
    assert got == (str(tmp_path), before)    # JAX reads it; code sets none
