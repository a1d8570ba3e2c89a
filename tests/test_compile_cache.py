"""Where the persistent compilation cache lives
(``repro.launch.compile_cache``): the deployment's
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``
whatever the working directory."""
import os

import jax
import pytest

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_var_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    assert compile_cache.compilation_cache_dir() == str(tmp_path / "cc")


@pytest.mark.parametrize("cwd", ["repo", "tmp", "tests"])
def test_repo_dir_whatever_the_cwd(monkeypatch, tmp_path, cwd):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir({"repo": REPO, "tmp": str(tmp_path),
                       "tests": os.path.join(REPO, "tests")}[cwd])
    assert compile_cache.compilation_cache_dir() == os.path.join(
        REPO, ".jax_cache")


def test_enable_and_disable_set_jax_config(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        assert compile_cache.enable_compilation_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        assert jax.config.jax_enable_compilation_cache is True
        assert compile_cache.enable_compilation_cache(enabled=False) is None
        assert jax.config.jax_enable_compilation_cache is False
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
