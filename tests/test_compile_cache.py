"""The persistent compilation cache helper: a fixed path per checkout,
and an operator's ``JAX_COMPILATION_CACHE_DIR`` left alone."""

from __future__ import annotations

from pathlib import Path

import pytest

from repro import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("env_dir", [None, "/srv/jax-cache"])
def test_use_compile_cache_directory(monkeypatch, env_dir):
    updates = {}
    monkeypatch.setattr(
        compile_cache.jax.config, "update", lambda k, v: updates.__setitem__(k, v)
    )
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)

    used = compile_cache.use_compile_cache()

    if env_dir is None:
        assert used == str(REPO / ".cache" / "jax_compile")
        assert updates["jax_compilation_cache_dir"] == used
    else:
        assert used == env_dir
        assert "jax_compilation_cache_dir" not in updates
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0
