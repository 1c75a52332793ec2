"""The persistent compilation cache helper shared by the entry points."""
import os
from pathlib import Path

import jax
import pytest

from repro.compile_cache import ENV_VAR, enable_compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_names_the_cache_and_is_left_alone(monkeypatch, tmp_path):
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert os.environ[ENV_VAR] == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_default_is_one_fixed_dir_in_the_repo(monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    first, second = enable_compile_cache(), enable_compile_cache()
    assert first == second == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert ENV_VAR not in os.environ
