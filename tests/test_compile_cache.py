"""The compile-cache helper every entry point calls
(shadow_tpu.utils.compile_cache): a set JAX_COMPILATION_CACHE_DIR wins
and no other directory is set in code; otherwise one fixed path in the
checkout."""

import jax
import pytest

from shadow_tpu.utils import compile_cache as CC


@pytest.fixture
def restore_cache_dir():
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


@pytest.mark.parametrize("env", ["set", "unset"])
def test_cache_dir_placement(env, tmp_path, monkeypatch, restore_cache_dir):
    jax.config.update("jax_compilation_cache_dir", "/untouched")
    if env == "set":
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert CC.enable_compile_cache() == str(tmp_path)
        # JAX reads the variable itself; the helper sets no directory
        assert jax.config.jax_compilation_cache_dir == "/untouched"
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(CC, "DEFAULT_DIR", str(tmp_path / "fixed"))
        assert CC.enable_compile_cache() == str(tmp_path / "fixed")
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "fixed")


def test_default_dir_is_in_the_checkout():
    import os

    assert CC.DEFAULT_DIR == os.path.join(CC.CHECKOUT, ".jax_cache")
    assert os.path.isfile(os.path.join(CC.CHECKOUT, "pyproject.toml"))
