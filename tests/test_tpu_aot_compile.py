"""The main path's Pallas kernels compile for a TPU v5e that is described,
not attached (libtpu's compiler is installed; no chip is needed).

A compile that passes is not a chip run — it says nothing about results or
times. It catches what interpret mode cannot: a block the chip's tiling
refuses, a kernel that wants more VMEM than it may have, a kernel that
cannot be partitioned. ``chip_smoke.py`` is the run on the chip.

Everything that touches the TPU compiler happens inside the module-scoped
``topo`` fixture or a test — nothing at import time (only one process may
hold libtpu, and every xdist worker imports every test file). Keep these
tests in this one file for the same reason.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# [BH, T, D] as the kernels see it. The first is the LM-large attention
# shape (batch 4 x 16 heads, T 2048, head_dim 64); d=128 is the head width
# of the configurations queued in ROADMAP R2-R4.
SHAPES = [
    pytest.param((64, 2048, 64), jnp.bfloat16, id="lm_large-bh64-t2048-d64-bf16"),
    pytest.param((64, 1024, 64), jnp.bfloat16, id="bh64-t1024-d64-bf16"),
    pytest.param((8, 2048, 128), jnp.bfloat16, id="bh8-t2048-d128-bf16"),
    pytest.param((8, 2048, 64), jnp.float32, id="bh8-t2048-d64-f32"),
]


def _blocks(t):
    from katib_tpu.ops import flash_attention as fa

    return (
        fa._auto_block(t, fa.FWD_BLOCK_Q_CAP),
        fa._auto_block(t, fa.FWD_BLOCK_K_CAP),
        fa._auto_block(t, fa.BWD_BLOCK_CAP),
    )


def _compile(fn, *avals):
    lowered = jax.jit(fn).lower(*avals)
    text = lowered.as_text()
    compiled = lowered.compile()  # raises what the chip's compiler would raise
    assert compiled.memory_analysis() is not None
    return text


@pytest.mark.parametrize("shape,dtype", SHAPES)
def test_flash_forward_kernel_compiles_for_v5e(one_chip, shape, dtype):
    from katib_tpu.ops import flash_attention as fa

    bq, bk, _ = _blocks(shape[1])
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    fwd = functools.partial(
        fa._fwd, causal=True, sm_scale=shape[2] ** -0.5,
        block_q=bq, block_k=bk, interpret=False,
    )
    text = _compile(fwd, x, x, x)
    assert text.count("tpu_custom_call") == 1


@pytest.mark.parametrize("shape,dtype", SHAPES)
def test_flash_backward_kernels_compile_for_v5e(one_chip, shape, dtype):
    """Both backward kernels (dq; dk/dv) at the tiles the VJP picks."""
    from katib_tpu.ops import flash_attention as fa

    _, _, bb = _blocks(shape[1])
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    lse = jax.ShapeDtypeStruct(shape[:2] + (1,), jnp.float32, sharding=one_chip)
    bwd = functools.partial(
        fa._bwd, causal=True, sm_scale=shape[2] ** -0.5,
        block_q=bb, block_k=bb, interpret=False,
    )
    text = _compile(bwd, x, x, x, x, lse, x)  # q k v o lse do
    assert text.count("tpu_custom_call") == 2


@pytest.mark.parametrize("shape,dtype", SHAPES)
def test_flash_value_and_grad_compiles_for_v5e(one_chip, shape, dtype):
    """The custom-VJP wrapper as a train step differentiates it: forward,
    residuals, and both backward kernels in one program."""
    from katib_tpu.ops import flash_attention as fa

    bq, bk, _ = _blocks(shape[1])
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v):
        o = fa._flash_bhtd(q, k, v, True, shape[2] ** -0.5, bq, bk, False)
        return o.astype(jnp.float32).sum()

    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), x, x, x)
    assert text.count("tpu_custom_call") == 3
