"""The backend-derived switches of ``repro.compat``: x64 scope, device
precision, Pallas interpret mode and the persistent compile cache."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import compat


def test_x64_scope_enables_and_restores():
    before = jax.config.jax_enable_x64
    with compat.x64(True):
        assert jnp.asarray(np.zeros(2)).dtype == jnp.float64
    with compat.x64(False):
        assert jnp.asarray(np.zeros(2)).dtype == jnp.float32
    assert jax.config.jax_enable_x64 == before


@pytest.mark.parametrize("x64", [True, False])
def test_device_precision_follows_backend(monkeypatch, x64):
    """f64 uploads on CPU; on an accelerator f32/int32 uploads and
    full-precision f32 matmuls."""
    monkeypatch.setattr(compat, "device_x64", lambda: x64)
    with compat.device_precision():
        assert jnp.asarray(np.zeros(2)).dtype == (jnp.float64 if x64 else jnp.float32)
        assert jnp.asarray(np.zeros(2, np.int64)).dtype == (
            jnp.int64 if x64 else jnp.int32
        )
        if not x64:
            assert jax.config.jax_default_matmul_precision == "highest"


def test_cpu_backend_runs_f64_and_interprets_kernels():
    assert compat.device_x64() is (jax.default_backend() == "cpu")
    assert compat.pallas_interpret() is True
    assert compat.pallas_interpret(jnp.zeros(1)) is True
    assert compat.pallas_interpret(np.zeros(1)) is True


@pytest.fixture
def cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_compile_cache_in_checkout_by_default(cache_config, monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compat.enable_compile_cache(str(tmp_path))
    assert path == str(tmp_path / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_env_dir_wins(cache_config, monkeypatch, tmp_path):
    env = str(tmp_path / "from_env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    before = jax.config.jax_compilation_cache_dir
    assert compat.enable_compile_cache(str(tmp_path)) == env
    # JAX reads the variable itself; the helper sets no other directory
    assert jax.config.jax_compilation_cache_dir == before
