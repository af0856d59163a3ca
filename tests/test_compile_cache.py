"""The persistent compilation cache's one fixed place."""
import os
from pathlib import Path

import jax
import pytest

from repro.utils import use_compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture
def saved_cache_dir():
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


def test_env_var_is_honoured_and_nothing_is_set(monkeypatch, tmp_path,
                                                saved_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_one_checkout_path_from_any_cwd(monkeypatch, tmp_path,
                                                   saved_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    expected = str(CHECKOUT / ".jax_cache")
    seen = []
    for cwd in (tmp_path, CHECKOUT / "src", Path("/")):
        monkeypatch.chdir(cwd)
        seen.append(use_compile_cache())
        assert jax.config.jax_compilation_cache_dir == expected
    assert seen == [expected] * 3
    assert os.path.isabs(expected)
