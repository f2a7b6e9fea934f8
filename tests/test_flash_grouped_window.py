"""The flash kernels with grouped KV heads and a window (Pallas interpret mode)
against dense masked attention written out here: the forward pass and all three
gradients, for groups of 1, 6 and 8 query heads a KV head, with no window, a
window narrower than a block, one as deep as a block (the sparse cell's ratio,
where a chunk takes static slices of its band), one of two blocks and one that
spans blocks; bfloat16 as the chip runs it; the first q block alone, whose band
the start of the sequence cuts."""

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


# (forward, gradients): float32 as the file always held them; bfloat16 as read
# off the parent's stepping kernels on these cases against _dense on the same
# rounded inputs, 7.5e-3 and 2.8e-2 at most: the outputs' own rounding
TOLERANCE = {jnp.float32: (2e-5, 5e-5), jnp.bfloat16: (1e-2, 3e-2)}


def _agree_with_dense(group, window, blocks, dtype=jnp.float32, rows=None, kv_heads=2, t=256):
    """Forward and the three gradients against _dense; ``rows``: of the first
    so many rows alone (the others' cotangent is zero)."""
    q, k, v, do = (x.astype(dtype) for x in _inputs(group, kv_heads=kv_heads, t=t))
    if rows is not None:
        do = do.at[:, rows:].set(0)

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=window, interpret=True,
                                  block_q=blocks[0], block_k=blocks[1])

    def f32(x):
        return x.astype(jnp.float32)

    o, pullback = jax.vjp(kernel, q, k, v)
    o_ref, pullback_ref = jax.vjp(lambda q, k, v: _dense(q, k, v, window), f32(q), f32(k), f32(v))
    fwd_tol, grad_tol = TOLERANCE[dtype]
    np.testing.assert_allclose(f32(o)[:, :rows], o_ref[:, :rows], atol=fwd_tol, rtol=fwd_tol)
    for got, want, name in zip(pullback(do), pullback_ref(f32(do)), "qkv"):
        assert got.dtype == dtype
        np.testing.assert_allclose(f32(got), want, atol=grad_tol, rtol=grad_tol, err_msg=f"d{name}")


@pytest.mark.parametrize("group,window,dtype,rows", [
    *[pytest.param(group, window, jnp.float32, None, id=f"{group}-{window}")
      for window in (None, 48, 200) for group in (1, 6, 8)],
    # the sparse cell's ratio, a window as deep as a block: blocks 1-3 take static slices
    pytest.param(1, 64, jnp.float32, None, id="1-64-window-is-a-block"),
    pytest.param(8, 64, jnp.float32, None, id="8-64-window-is-a-block"),
    pytest.param(6, 128, jnp.float32, None, id="6-128-window-of-two-blocks"),
    pytest.param(6, 16, jnp.float32, None, id="6-16-window-narrower-than-a-chunk"),
    pytest.param(6, 64, jnp.bfloat16, None, id="6-64-bfloat16"),
    pytest.param(1, 200, jnp.bfloat16, None, id="1-200-bfloat16"),
    # the band cut by the start of the sequence
    pytest.param(6, 64, jnp.float32, 64, id="6-64-first-q-block-alone"),
    pytest.param(8, 200, jnp.float32, 64, id="8-200-first-q-block-alone"),
])
def test_kernels_agree_with_dense_masked_attention(group, window, dtype, rows):
    _agree_with_dense(group, window, (64, 64), dtype, rows)


@pytest.mark.parametrize("blocks,window,dtype,t", [
    # q blocks of two kv blocks, as the chip runs it (1,024 over 512): forward and dq take three kv
    # blocks in turn, dk/dv kv blocks as wide as the q block
    pytest.param((128, 64), 100, jnp.float32, 256, id="blocks0"),
    pytest.param((128, 64), 64, jnp.float32, 256, id="128x64-window-64"),
    pytest.param((128, 64), 100, jnp.bfloat16, 256, id="128x64-bfloat16"),
    # kv blocks of two q blocks: forward and dq take every operand whole, dk/dv static slices
    pytest.param((64, 128), 100, jnp.float32, 256, id="blocks1"),
    pytest.param((64, 128), 128, jnp.float32, 256, id="64x128-window-128"),
    pytest.param((64, 128), 100, jnp.bfloat16, 256, id="64x128-bfloat16"),
    # neither divides the other: all three take every operand whole
    pytest.param((128, 192), 100, jnp.float32, 384, id="128x192-no-static-walk"),
    pytest.param((256, 256), 48, jnp.float32, 256, id="one-block-holds-the-sequence"),
])
def test_unequal_blocks_walk_the_same_band(blocks, window, dtype, t):
    _agree_with_dense(6, window, blocks, dtype, kv_heads=1, t=t)


def _scores(t, window, block_q, block_k):
    """(computed, visible) scores of the windowed forward: a q block whose band
    reaches before the sequence computes what every other does."""
    count = fa._band_kv_blocks(t, window, block_q, block_k)
    plan = fa._band_plan(block_q, block_k, count, window, False)
    chunk = block_q // len(plan)
    computed = t // block_q * sum(chunk * (stop - start) for pieces in plan for _, start, stop, _ in pieces)
    return computed, sum(min(row + 1, window) for row in range(t))


def test_a_step_holds_the_band_and_computes_little_beyond_it():
    # T 8192, window 512, blocks of 512: two kv blocks a q block, two q blocks a kv block
    assert fa._band_kv_blocks(8192, 512, 512, 512) == 2
    assert fa._band_q_blocks(8192, 512, 512, 512) == 2
    assert fa._band_kv_blocks(8192, 512, 256, 256) == 3
    assert fa._band_kv_blocks(256, 48, 64, 64) == 2
    # a chunk of 128 rows computes 640 columns for the 512 its rows see; the stepping grid computed 1,024
    computed, visible = _scores(8192, 512, 512, 512)
    assert 1.0 <= computed / visible <= 1.3
    # every piece is a slice of one operand, and only pieces at the band's two edges are masked
    for pieces in fa._band_plan(512, 512, 2, 512, False):
        assert all(0 <= start < stop <= 512 and operand in (0, 1) for operand, start, stop, _ in pieces)
        tiles = [jnp.zeros((128, stop - start)) for _, start, stop, _ in pieces]
        masked = [fa._mask_band(tile, gap0, 512) is not tile for tile, (_, _, _, gap0) in zip(tiles, pieces)]
        assert masked == [True] + [False] * (len(pieces) - 2) + [True], pieces
    # q blocks of 64 under kv blocks of 128: forward and dq have no static walk, dk/dv has
    assert fa._band_plan(64, 128, 2, 100, False) is None
    assert fa._band_plan(128, 64, fa._band_q_blocks(256, 100, 64, 128), 100, True) is not None


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
