"""tensor/backend.init_backend: a backend that cannot initialize raises
(nothing continues on another platform), and the compile cache has one
place."""

import os

import jax
import pytest

from nomad_tpu.tensor import backend

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir_config():
    """Restore jax_compilation_cache_dir after the test."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_a_backend_that_cannot_initialize_raises(monkeypatch):
    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", no_backend)
    platforms = jax.config.jax_platforms
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        backend.init_backend()
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        backend.device_info()
    assert jax.config.jax_platforms == platforms


def test_cache_goes_to_one_fixed_place_in_the_checkout(monkeypatch,
                                                       cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    assert backend.init_backend() == jax.devices()
    assert jax.config.jax_compilation_cache_dir == backend.COMPILE_CACHE_DIR
    assert backend.COMPILE_CACHE_DIR == os.path.join(ROOT, ".jax_cache")


def test_the_environments_cache_directory_is_left_to_jax(monkeypatch,
                                                         tmp_path,
                                                         cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    backend.init_backend()
    assert jax.config.jax_compilation_cache_dir is None


def test_device_info_is_what_jax_reports():
    info = backend.device_info()
    assert info == {"platform": jax.devices()[0].platform,
                    "kind": jax.devices()[0].device_kind,
                    "count": len(jax.devices())}
