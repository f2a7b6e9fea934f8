"""Hardware-gated validation of the Pallas kernels (VERDICT round-1 item 3).

These tests only run against a real TPU backend (``KATIB_TPU_TEST_TPU=1
python -m pytest tests/test_tpu_hardware.py``) — off-TPU the flash-attention
wrapper takes the dense/interpret fallback, which validates semantics but
not Mosaic compilation, the scratch padding, or the backward kernels.
What the kernels cost at the listed cells' shapes is the benchmark's to say
(``benchmarks/``: the ``flash_*_roofline`` metrics of a traced run).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from katib_tpu.ops.flash_attention import flash_attention
from katib_tpu.ops.ring_attention import dense_attention


@pytest.fixture
def real_tpu():
    """Skips without a real TPU. Asked inside a fixture, never while the
    module is imported: every xdist worker imports every test file, and all
    of them must collect the same tests."""
    if jax.devices()[0].platform == "cpu":
        pytest.skip("needs a real TPU backend (KATIB_TPU_TEST_TPU=1)")


requires_tpu = pytest.mark.usefixtures("real_tpu")


def _rand(b, t, h, d, dtype, seed=0):
    rng = np.random.default_rng(seed)
    return (
        jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=dtype),
        jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=dtype),
        jnp.asarray(rng.standard_normal((b, t, h, d)), dtype=dtype),
    )


@requires_tpu
@pytest.mark.parametrize("t", [128, 1024])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_forward_matches_dense_compiled(t, causal, dtype):
    q, k, v = _rand(2, t, 4, 64, dtype)
    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=causal))(q, k, v)
    # reference at HIGHEST precision: TPU f32 matmuls default to a bf16
    # decomposition (~1e-3 error), which would dominate the comparison
    with jax.default_matmul_precision("highest"):
        ref = dense_attention(
            q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
            causal=causal,
        )
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), np.asarray(ref), atol=tol, rtol=tol
    )


@requires_tpu
@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_dense_compiled(causal):
    q, k, v = _rand(2, 256, 4, 64, jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=causal) ** 2)

    gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    with jax.default_matmul_precision("highest"):
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3, rtol=2e-3)


@requires_tpu
def test_flash_not_slower_than_dense_at_long_seq():
    """The kernel must beat plain XLA attention at T=2048 bf16 — if it
    doesn't, the block sizes need fixing (VERDICT: 'if the kernel isn't
    faster, say so')."""
    import time

    q, k, v = _rand(4, 2048, 8, 64, jnp.bfloat16)
    flash = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    dense = jax.jit(lambda q, k, v: dense_attention(q, k, v, causal=True))

    # chain outputs into inputs and end with one host read, so the whole
    # loop pays one synchronisation (katib_tpu.utils.timing)
    from katib_tpu.utils.timing import host_sync, roundtrip_ms

    rt_s = roundtrip_ms() / 1e3

    def timeit(fn, n=50):
        host_sync(fn(q, k, v))
        t0 = time.time()
        out = q
        for _ in range(n):
            out = fn(out, k, v)
        host_sync(out)
        return max((time.time() - t0 - rt_s) / n, 1e-9)

    flash_s, dense_s = timeit(flash), timeit(dense)
    print(f"flash {flash_s*1e3:.3f}ms dense {dense_s*1e3:.3f}ms "
          f"speedup {dense_s/flash_s:.2f}x")
    assert flash_s <= dense_s * 1.1, (
        f"flash ({flash_s*1e3:.2f}ms) slower than dense ({dense_s*1e3:.2f}ms)"
    )


@requires_tpu
def test_lm_train_step_compiles_and_runs_on_tpu():
    """One real train step of the flagship LM path on hardware."""
    from katib_tpu.models.transformer import TransformerConfig
    from katib_tpu.parallel.mesh import make_mesh
    from katib_tpu.parallel.train import make_lm_train_step

    config = TransformerConfig(
        vocab_size=512, embed_dim=128, num_layers=2, num_heads=4,
        max_seq_len=256, dtype=jnp.bfloat16,
    )
    mesh = make_mesh(jax.devices()[:1])
    params, opt_state, step_fn, put_batch = make_lm_train_step(config, mesh, 1e-3)
    rng = np.random.default_rng(0)
    d = rng.integers(0, 512, size=(4, 257), dtype=np.int32)
    tokens, targets, positions = put_batch(d[:, :-1], d[:, 1:])
    params, opt_state, loss = step_fn(params, opt_state, tokens, targets, positions)
    assert np.isfinite(float(loss))
