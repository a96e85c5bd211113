"""Placement of the persistent compilation cache (repro.launch.compile_cache)."""

from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import use_compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_use_compile_cache_placement(env_dir, tmp_path, monkeypatch):
    """Set, ``JAX_COMPILATION_CACHE_DIR`` wins and no config is touched;
    unset, the cache is ``<checkout>/.jax_cache`` from any working dir."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.chdir(tmp_path)
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
    try:
        got = use_compile_cache()
        after = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if env_dir is None:
        assert got == str(CHECKOUT / ".jax_cache")
        assert after == got
    else:
        assert got == str(tmp_path / env_dir)
        assert after == before
