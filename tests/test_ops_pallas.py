"""Pallas flash-attention kernel vs dense reference (interpret mode on the
8-device CPU mesh from conftest)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from katib_tpu.ops.flash_attention import flash_attention, sharded_flash_attention
from katib_tpu.ops.ring_attention import dense_attention
from katib_tpu.parallel.mesh import make_mesh


def _qkv(b=2, t=128, h=4, d=16, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.normal(size=(b, t, h, d)), dtype=jnp.float32)
        for _ in range(3)
    )


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_dense(causal):
    q, k, v = _qkv()
    o = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_dense(causal):
    q, k, v = _qkv()

    def f(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=64, block_k=64).sum()

    def ref(q, k, v):
        return dense_attention(q, k, v, causal=causal).sum()

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_uneven_blocks_use_multiple_kv_steps():
    # block_q != block_k and several grid steps along each axis
    q, k, v = _qkv(t=256)
    o = flash_attention(q, k, v, causal=True, block_q=64, block_k=32)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=1e-5)


def test_tiny_sequence_falls_back_to_dense():
    q, k, v = _qkv(t=7)
    o = flash_attention(q, k, v, causal=True)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=1e-5)


def test_bfloat16_inputs():
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv())
    o = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = dense_attention(q, k, v, causal=True)
    assert o.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(o, dtype=np.float32), np.asarray(ref, dtype=np.float32), atol=3e-2
    )


def test_sharded_flash_attention_matches_dense():
    q, k, v = _qkv(b=4)
    mesh = make_mesh(data=2, fsdp=2, model=2)
    o = sharded_flash_attention(q, k, v, mesh, causal=True, block_q=64, block_k=64)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=1e-5)

    g = jax.grad(lambda q: sharded_flash_attention(q, k, v, mesh, causal=True).sum())(q)
    gr = jax.grad(lambda q: dense_attention(q, k, v, causal=True).sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=1e-4)


def test_auto_block_lane_aligned():
    """Auto-picked blocks must be 128-aligned divisors of T; shapes without
    one fall back to dense (t % block != 0 at the call site)."""
    from katib_tpu.ops.flash_attention import _auto_block

    assert _auto_block(2048, 1024) == 1024
    assert _auto_block(1536, 1024) == 768
    assert _auto_block(384, 1024) == 384
    assert _auto_block(128, 1024) == 128
    assert _auto_block(192, 1024) is None  # 192 divides itself but isn't 128-aligned
    assert _auto_block(960, 1024) is None
    assert _auto_block(100, 1024) is None
    for t in (256, 512, 1024, 4096, 8192):
        b = _auto_block(t, 1024)
        assert b is not None and b % 128 == 0 and t % b == 0


def test_with_lse_merge_equals_full_attention():
    """Splitting K/V into blocks, attending each with flash_attention_with_lse
    and folding via merge_attention_blocks must equal attention over the full
    sequence — the invariant ring attention is built on."""
    from katib_tpu.ops.flash_attention import (
        flash_attention_with_lse,
        merge_attention_blocks,
    )

    rng = np.random.default_rng(3)
    b, t, h, d = 2, 64, 2, 8
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=jnp.float32)

    full = dense_attention(q, k, v, causal=False)

    o1, l1 = flash_attention_with_lse(q, k[:, : t // 2], v[:, : t // 2])
    o2, l2 = flash_attention_with_lse(q, k[:, t // 2 :], v[:, t // 2 :])
    merged, lse = merge_attention_blocks(o1, l1, o2, l2)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(full), atol=2e-5, rtol=2e-5)

    # merging with a fully-masked partial is the identity
    masked_o = jnp.zeros_like(o1)
    masked_l = jnp.full_like(l1, -1e30)
    same, same_l = merge_attention_blocks(merged, lse, masked_o, masked_l)
    np.testing.assert_allclose(np.asarray(same), np.asarray(merged), atol=1e-6)
    np.testing.assert_allclose(np.asarray(same_l), np.asarray(lse), atol=1e-6)


def test_with_lse_kernel_matches_fallback_interpret():
    """The Pallas path of flash_attention_with_lse (interpret mode off-TPU)
    must produce the same (o, lse) as the dense fallback."""
    from katib_tpu.ops.flash_attention import flash_attention_with_lse

    rng = np.random.default_rng(4)
    b, t, h, d = 1, 128, 2, 8
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=jnp.float32)
    for causal in (False, True):
        o_ref, l_ref = flash_attention_with_lse(q, k, v, causal=causal, interpret=False)
        o_k, l_k = flash_attention_with_lse(q, k, v, causal=causal, interpret=True)
        np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_ref), atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(l_k), np.asarray(l_ref), atol=2e-5, rtol=2e-5)


def test_block_grads_kernel_matches_fallback_interpret():
    """The Pallas _bwd path of flash_block_grads (interpret mode off-TPU)
    must match the dense-fallback block gradients — covers the ring-attention
    backward's kernel glue in CI (previously only reachable on hardware)."""
    from katib_tpu.ops.flash_attention import (
        flash_attention_with_lse,
        flash_block_grads,
    )

    rng = np.random.default_rng(5)
    b, t, h, d = 1, 128, 2, 8
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=jnp.float32)
    do = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=jnp.float32)
    for causal in (False, True):
        o, lse = flash_attention_with_lse(q, k, v, causal=causal, interpret=False)
        ref = flash_block_grads(q, k, v, o, lse, do, causal=causal, interpret=False)
        ker = flash_block_grads(q, k, v, o, lse, do, causal=causal, interpret=True)
        for r, kk, name in zip(ref, ker, ("dq", "dk", "dv")):
            np.testing.assert_allclose(
                np.asarray(kk), np.asarray(r), atol=5e-5, rtol=5e-5,
                err_msg=f"{name} causal={causal}",
            )


def test_ring_backward_kernel_path_matches_dense_grad():
    """jax.grad through the ring (kernel path forced via interpret=True on
    both the fwd flash and the bwd block-grad kernels) equals the dense
    attention gradient — the full ring VJP with Pallas kernels in CI."""
    import functools

    from katib_tpu.ops.ring_attention import dense_attention, ring_attention_local
    from katib_tpu.parallel.mesh import make_mesh
    from jax.sharding import PartitionSpec as P

    devices = jax.devices()[:4]
    mesh = make_mesh(devices, seq=4, data=1)
    rng = np.random.default_rng(6)
    b, t, h, d = 1, 128, 2, 8
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=jnp.float32)

    spec = P(None, "seq", None, None)
    for causal in (False, True):
        ring = jax.shard_map(
            functools.partial(
                ring_attention_local, axis_name="seq", causal=causal,
                interpret=True,  # force the Pallas kernels off-TPU
            ),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )
        g_ring = jax.grad(lambda q, k, v: (ring(q, k, v) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(
            lambda q, k, v: (dense_attention(q, k, v, causal=causal) ** 2).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        for gr, gd, name in zip(g_ring, g_ref, ("dq", "dk", "dv")):
            np.testing.assert_allclose(
                np.asarray(gr), np.asarray(gd), atol=2e-4, rtol=2e-4,
                err_msg=f"{name} causal={causal}",
            )


def test_on_tpu_asks_the_backend_plainly(monkeypatch):
    """An error while asking the backend is an error: answering False would
    hand a TPU host dense attention in the kernel's place, unseen."""
    from katib_tpu.ops import flash_attention as fa
    from katib_tpu.utils import backend

    def _dead(*_a, **_k):
        raise backend.BackendUnavailable("no backend")

    fa._on_tpu.cache_clear()
    monkeypatch.setattr(backend, "require_devices", _dead)
    try:
        with pytest.raises(backend.BackendUnavailable):
            fa._use_kernel(None)
        assert fa._use_kernel(True) is True  # forced paths never ask
        assert fa._use_kernel(False) is False
    finally:
        fa._on_tpu.cache_clear()
    monkeypatch.undo()
    assert fa._on_tpu() is False  # the CPU backend answers, plainly
    fa._on_tpu.cache_clear()
