"""The flash kernels with grouped KV heads and a window (Pallas interpret mode)
against dense masked attention written out here: the forward pass and all three
gradients, for groups of 1, 6 and 8 query heads a KV head, with no window, a
window narrower than a block and one that spans blocks."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from katib_tpu.ops import flash_attention as fa


def _dense(q, k, v, window):
    """softmax(q.k / sqrt(d)) v over the keys s with 0 <= t - s (< window);
    query head j reads KV head j // group. float32, highest precision."""
    b, t, h, d = q.shape
    group = h // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / math.sqrt(d)
    gap = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    seen = (gap >= 0) if window is None else (gap >= 0) & (gap < window)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")


def _inputs(group, kv_heads=2, t=256, d=64, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(keys[0], (1, t, kv_heads * group, d), jnp.float32)
    k = jax.random.normal(keys[1], (1, t, kv_heads, d), jnp.float32)
    v = jax.random.normal(keys[2], (1, t, kv_heads, d), jnp.float32)
    do = jax.random.normal(keys[3], q.shape, jnp.float32)
    return q, k, v, do


@pytest.mark.parametrize("window", [None, 48, 200])
@pytest.mark.parametrize("group", [1, 6, 8])
def test_kernels_agree_with_dense_masked_attention(group, window):
    q, k, v, do = _inputs(group)

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=window, interpret=True,
                                  block_q=64, block_k=64)

    o, pullback = jax.vjp(kernel, q, k, v)
    o_ref, pullback_ref = jax.vjp(lambda q, k, v: _dense(q, k, v, window), q, k, v)
    np.testing.assert_allclose(o, o_ref, atol=2e-5, rtol=2e-5)
    for got, want, name in zip(pullback(do), pullback_ref(do), "qkv"):
        np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5, err_msg=f"d{name}")


@pytest.mark.parametrize("blocks", [(128, 64), (64, 128)])
def test_unequal_blocks_walk_the_same_band(blocks):
    q, k, v, do = _inputs(6, kv_heads=1)
    o, pullback = jax.vjp(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, window=100, interpret=True, block_q=blocks[0], block_k=blocks[1]), q, k, v)
    o_ref, pullback_ref = jax.vjp(lambda q, k, v: _dense(q, k, v, 100), q, k, v)
    np.testing.assert_allclose(o, o_ref, atol=2e-5, rtol=2e-5)
    for got, want in zip(pullback(do), pullback_ref(do)):
        np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5)


def test_the_band_s_grid_is_as_long_as_the_band_not_as_the_sequence():
    # T 8192, window 512, blocks of 512: two kv blocks a q block, two q blocks a kv block
    assert fa._band_kv_steps(8192, 512, 512, 512) == 2
    assert fa._band_q_steps(8192, 512, 512, 512) == 2
    assert fa._band_kv_steps(8192, 512, 256, 256) == 3
    assert fa._band_kv_steps(256, 48, 64, 64) == 2


@pytest.mark.parametrize("group,window", [(1, None), (6, None), (8, 48)])
def test_the_fallback_off_the_chip_is_the_same_function(group, window):
    q, k, v, _ = _inputs(group)
    o = fa.flash_attention(q, k, v, causal=True, window=window, interpret=False)
    np.testing.assert_allclose(o, _dense(q, k, v, window), atol=2e-5, rtol=2e-5)


def test_what_is_refused():
    q, k, v, _ = _inputs(6)
    with pytest.raises(ValueError, match="share"):
        fa.flash_attention(q[:, :, :5], k, v, causal=True)
    with pytest.raises(ValueError, match="causal band"):
        fa.flash_attention(q, k, v, causal=False, window=16)


def test_windowed_calls_carry_their_own_kernel_names():
    q, k, v, _ = _inputs(6, t=128)
    def names(window):
        text = str(jax.make_jaxpr(jax.grad(lambda q: fa.flash_attention(
            q, k, v, causal=True, window=window, interpret=True, block_q=64, block_k=64).sum()))(q))
        return {n for n in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "flash_window_fwd",
                            "flash_window_bwd_dq", "flash_window_bwd_dkv") if f"name={n}" in text or f"{n} " in text or f'"{n}"' in text}
    assert names(None) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    assert names(32) == {"flash_window_fwd", "flash_window_bwd_dq", "flash_window_bwd_dkv"}
