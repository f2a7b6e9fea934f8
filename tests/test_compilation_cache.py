"""Persistent compile cache: where it goes and when it is switched on.

The platform decision is the pure function ``_accelerator_platform`` over
config/env/accelerator hints — it never initializes a backend (the process
that does owns the chip). The directory follows two rules: JAX's own
``JAX_COMPILATION_CACHE_DIR`` when set (and then no directory is set from
code), else the fixed in-checkout path."""

import os

import pytest

from katib_tpu.utils import compilation
from katib_tpu.utils.compilation import _accelerator_platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_explicit_cpu_skips():
    assert _accelerator_platform("cpu", environ={}, libtpu_present=True) is False
    assert _accelerator_platform("cpu,tpu", environ={}, libtpu_present=True) is False


def test_explicit_accelerator_enables():
    assert _accelerator_platform("tpu", environ={}, libtpu_present=False) is True
    assert _accelerator_platform("tpu,cpu", environ={}, libtpu_present=False) is True
    assert _accelerator_platform("cuda", environ={}, libtpu_present=False) is True


def test_auto_detect_cpu_only_host_skips():
    assert _accelerator_platform("", environ={}, libtpu_present=False) is False


def test_auto_detect_with_libtpu_enables():
    assert _accelerator_platform("", environ={}, libtpu_present=True) is True


def test_auto_detect_with_tpu_name_env_enables():
    assert (
        _accelerator_platform("", environ={"TPU_NAME": "pod0"}, libtpu_present=False)
        is True
    )


@pytest.fixture
def cache_updates(monkeypatch):
    """Run enable_compilation_cache as if on an accelerator host, recording
    every jax.config.update it makes instead of applying it."""
    import jax

    updates = {}
    monkeypatch.setattr(compilation, "_initialized", False)
    monkeypatch.setattr(compilation, "_accelerator_platform", lambda *_a, **_k: True)
    monkeypatch.setattr(jax.config, "update", lambda k, v: updates.__setitem__(k, v))
    return updates


def test_env_dir_is_used_and_not_set_from_code(monkeypatch, tmp_path, cache_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside"))
    assert compilation.enable_compilation_cache() == str(tmp_path / "outside")
    assert "jax_compilation_cache_dir" not in cache_updates
    assert cache_updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
    # the program creates nothing there either: the directory is JAX's
    assert not (tmp_path / "outside").exists()


def test_unset_env_uses_fixed_in_checkout_path(monkeypatch, cache_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    expected = os.path.join(REPO, ".katib-tpu", "xla-cache")
    assert compilation.enable_compilation_cache() == expected
    assert cache_updates["jax_compilation_cache_dir"] == expected
    # idempotent, and the same path on every call (the path is in the key)
    assert compilation.enable_compilation_cache() == expected


def test_cache_dir_follows_the_environment_without_jax(monkeypatch, tmp_path):
    """What the telemetry sampler scans: the same two rules, no JAX import."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compilation.cache_dir() == os.path.join(REPO, ".katib-tpu", "xla-cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compilation.cache_dir() == str(tmp_path)


def test_cpu_platform_enables_nothing(monkeypatch):
    import jax

    updates = {}
    monkeypatch.setattr(compilation, "_initialized", False)
    monkeypatch.setattr(jax.config, "update", lambda k, v: updates.__setitem__(k, v))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    compilation.enable_compilation_cache()
    assert updates == {}
