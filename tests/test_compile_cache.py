"""The launchers' persistent compile cache: where it lives, what it keeps."""

import pathlib

import jax
import pytest

import repro

from repro.runtime.compile_cache import REPO_CACHE_DIR, enable_compile_cache


@pytest.fixture
def restore_cache_config():
    keys = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
    )
    prev = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in prev.items():
        jax.config.update(k, v)
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_cache_dir_and_threshold(env_dir, monkeypatch, tmp_path,
                                 restore_cache_config):
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(REPO_CACHE_DIR)
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == want
    # A set variable is JAX's to read; nothing is written over it here.
    after = jax.config.jax_compilation_cache_dir
    assert after == (before if env_dir else want)
    # Short compiles are kept too.
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_repo_cache_dir_is_in_the_checkout():
    root = REPO_CACHE_DIR.parent
    assert REPO_CACHE_DIR.name == ".jax_cache"
    assert (root / "src" / "repro").resolve() == (
        pathlib.Path(repro.__file__).resolve().parent
    )
