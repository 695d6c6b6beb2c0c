"""The kernels' interpret decision and the compile-cache placement."""

import jax
import pytest

from repro import compile_cache
from repro.kernels import backend


def test_cpu_backend_interprets():
    assert jax.default_backend() == "cpu"
    assert backend.interpret() is True
    assert backend.mode() == "interpreted"


@pytest.mark.parametrize("platform,expect", [("tpu", False), ("gpu", None),
                                             ("cuda", None)])
def test_decision_follows_platform(monkeypatch, platform, expect):
    monkeypatch.setattr(backend.jax, "default_backend", lambda: platform)
    if expect is None:
        with pytest.raises(RuntimeError, match=platform):
            backend.interpret()
    else:
        assert backend.interpret() is expect
        assert backend.mode() == "compiled"


@pytest.fixture
def cache_dir_restored():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_defaults_to_checkout(monkeypatch,
                                            cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.configure()
    assert path == compile_cache.DEFAULT_DIR
    assert path.endswith(".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_leaves_env_setting_to_jax(monkeypatch, tmp_path,
                                                  cache_dir_restored):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.configure() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
