"""Test configuration: force JAX onto 8 virtual CPU devices so multi-chip
sharding paths (Mesh/pjit/shard_map) are exercised without TPU hardware.

Set ``KATIB_TPU_TEST_TPU=1`` to skip the CPU forcing and run against the
real accelerator instead — this opens the hardware-gated tests in
``test_tpu_hardware.py`` (everything else still passes; meshes built from
``jax.devices()`` just see the real topology).
"""

import os

if os.environ.get("KATIB_TPU_TEST_TPU") != "1":
    # tests run on the virtual CPU mesh for determinism and an 8-device
    # sharding topology; set before any backend initializes
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    os.environ.setdefault("JAX_ENABLE_X64", "0")

    import jax

    jax.config.update("jax_platforms", "cpu")


def load_bench_module():
    """Load repo-root bench.py as a module (test_bench_budget's fixture)."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench.py")
    spec = importlib.util.spec_from_file_location("bench_under_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
