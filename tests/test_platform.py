"""Settings derived from the platform: interpret mode, the machine model,
and the persistent compile cache."""
import os
import types

import jax
import jax.numpy as jnp
import pytest

from repro.core import engine, use
from repro.core.config import get_config, resolve_interpret
from repro.core.machine import DEFAULT_MACHINE, TPU_V5E, machine_for_device
from repro.kernels.gemm import gemm


def _on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_interpret_defaults_from_platform():
    assert get_config().interpret is None
    # The test backend is the CPU: kernels run in the interpreter.
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False
    assert resolve_interpret(True) is True


def test_interpret_refused_on_tpu(monkeypatch):
    _on_tpu(monkeypatch)
    assert resolve_interpret(None) is False
    assert resolve_interpret(False) is False
    with pytest.raises(ValueError, match="TPU is present"):
        resolve_interpret(True)


@pytest.mark.parametrize("platform,kind,want", [
    ("tpu", "TPU v5 lite", TPU_V5E),
    ("cpu", "cpu", DEFAULT_MACHINE),
])
def test_machine_model_from_device_kind(platform, kind, want):
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    assert machine_for_device(dev) is want


def test_unknown_tpu_kind_is_an_error():
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v9 giant")
    with pytest.raises(ValueError, match="no machine model"):
        machine_for_device(dev)


def test_engine_config_machine_follows_first_device():
    cfg = get_config()
    assert cfg.machine is None
    assert cfg.machine_model is machine_for_device(jax.devices()[0])
    with use(machine="cpu_host") as pinned:
        assert pinned.machine_model.name == "cpu_host"


def test_stats_count_fused_launches():
    a = jnp.ones((64, 128), jnp.float32)
    b = jnp.ones((128, 128), jnp.float32)
    engine.reset_stats()
    with use(backend="pallas"):
        gemm(a, b, fused=True)
        gemm(a, b, fused=False)
    s = engine.stats()["gemm"]
    assert s["launches_fused"] == 1
    assert s["launches"] > s["launches_fused"]


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_follows_env(monkeypatch, restore_cache_dir):
    from repro.launch.compile_cache import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert enable_compile_cache() == "/elsewhere/cache"
    # JAX reads the variable itself: nothing is configured in code.
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch,
                                                      restore_cache_dir):
    from repro.launch import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == compile_cache.CHECKOUT_CACHE_DIR
    assert os.path.basename(path) == ".jax_cache"
    assert os.path.isfile(os.path.join(os.path.dirname(path),
                                       "pyproject.toml"))
    assert jax.config.jax_compilation_cache_dir == path
