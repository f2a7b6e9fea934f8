"""Kimi delta attention's chunked scan (katib_tpu/ops/kda.py) against the
recurrence written out a position at a time: the output and the gradients of
all five inputs, with the Pallas kernels interpreted (as they run off the
TPU); gates drawn anywhere in (-5, 0) and at the -5 bound, β near 1; values as
wide as the keys and twice as wide; a sequence that is no whole number of
chunks; float32 and bfloat16. The chunk parts from the kernel ``kda_chunks``,
and their pullback from the kernel ``kda_chunks_bwd``, against the XLA
``_within_chunks`` and its ``jax.vjp``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from katib_tpu.ops import kda as K


def _inputs(t=192, h=2, dk=32, dv=32, gate="random", seed=0):
    """q, k L2-normalised as the layer hands them; β near 1 (σ of N(3, 1));
    log α from the safe gate's range, or all at its bound."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(keys[0], (1, t, h, dk))
    k = jax.random.normal(keys[1], (1, t, h, dk))
    q, k = (x / jnp.linalg.norm(x, axis=-1, keepdims=True) for x in (q, k))
    v = jax.random.normal(keys[2], (1, t, h, dv))
    if gate == "bound":
        g = jnp.full((1, t, h, dk), -5.0 * 0.9999)
    else:
        g = -5.0 * jax.nn.sigmoid(2.0 * jax.random.normal(keys[3], (1, t, h, dk)))
    beta = jax.nn.sigmoid(3.0 + jax.random.normal(keys[4], (1, t, h)))
    do = jax.random.normal(keys[5], (1, t, h, dv))
    return q, k, v, g, beta, do


# (output, gradients), relative to the largest reference entry. float32: the
# chunked form and the recurrence sum in different orders, read at 4e-6 / 7e-6
# at most; bfloat16: products of bfloat16 operands (2^-8 each) summed over up
# to 64 positions and carried through the state, read at 1.2e-2 / 2.4e-2 at most
TOLERANCE = {jnp.float32: (2e-5, 3e-5), jnp.bfloat16: (3e-2, 6e-2)}
# The gate's gradient is held to the larger of its own largest entry and dk's:
# the chunked form's dg is a sum of terms of dk's size (a pair of positions
# each) that cancel, to ~1e-3 of it at the bound, where α = e^-5. Its error is
# those terms' rounding: 2e-6 of dk's largest entry in float32 and 2.0e-3 in
# bfloat16, at either gate (at the bound that is 1.4e-3 and 1.4 of dg's own
# largest entry; a float32 recurrence reads 1.3e-7 against a float64 one)
GATE = {jnp.float32: 2e-5, jnp.bfloat16: 1e-2}


@pytest.mark.parametrize("gate", ["random", "bound"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("dv", [32, 64], ids=["v32", "v64"])
def test_chunked_scan_is_the_recurrence(gate, dtype, dv):
    q, k, v, g, beta, do = _inputs(gate=gate, dv=dv)
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    o, pullback = jax.vjp(K.kda, q, k, v, g, beta)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    want, pullback_ref = jax.vjp(K.kda_recurrence, *f32, g, beta)
    fwd, grad = TOLERANCE[dtype]
    assert o.dtype == dtype and o.shape == want.shape
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(o, np.float32), want, atol=fwd * scale)
    refs = pullback_ref(do)
    for got, ref, name in zip(pullback(do.astype(dtype)), refs, ("q", "k", "v", "g", "beta")):
        assert np.all(np.isfinite(np.asarray(got, np.float32))), name
        scale = float(jnp.max(jnp.abs(ref)))
        if name == "g":
            scale, tol = max(scale, float(jnp.max(jnp.abs(refs[1])))), GATE[dtype]
        else:
            tol = grad
        np.testing.assert_allclose(np.asarray(got, np.float32), ref, atol=tol * scale, err_msg=f"d{name}")


# The chunk parts from ``kda_chunks`` against ``_within_chunks``, relative to
# each part's largest entry. float32: the kernel sums g in another order, and a
# decay's exponent is a difference of such sums, which reach 64 * 5 in size:
# an ulp of those, 2^-24 * 320 = 1.9e-5, is a decay's rounding (read: 6.1e-6 at
# most). bfloat16: one rounding of the parts' dtype, 2^-8 (read: 2.3e-3 at most)
PARTS = {jnp.float32: 2e-5, jnp.bfloat16: 2.0 ** -8}


@pytest.mark.parametrize("t", [256, 100], ids=["whole_chunks", "padded"])
@pytest.mark.parametrize("gate", ["random", "bound"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("dv", [32, 64], ids=["v32", "v64"])
def test_the_chunks_kernel_gives_the_parts(t, gate, dtype, dv):
    q, k, v, g, beta, _ = _inputs(t=t, gate=gate, dv=dv)
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    pad = -t % (K.CHUNK * K.PACK)  # as ``kda`` pads
    q, k, v, g = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) for x in (q, k, v, g))
    beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    got = K._chunk_parts(q, k, v, g, beta, 0.2, True)
    want = K._parts(q, k, v, g, beta, 0.2)
    for name, a, b in zip(("Q̃", "K_C", "W", "U0", "M", "decay"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        b = np.asarray(b, np.float32)
        np.testing.assert_allclose(np.asarray(a, np.float32), b, err_msg=name,
                                   atol=PARTS[dtype] * float(np.max(np.abs(b))))


# The pullback from ``kda_chunks_bwd`` against ``jax.vjp`` of ``_within_chunks``,
# each input's cotangent relative to its largest reference entry (dg: as GATE
# holds it). float32: a decay's rounding (above) enters through a row's and a
# column's decay, read at 2.0e-5 at most (dk). bfloat16: the reference rounds
# the cotangents of its bfloat16 operands (2^-8 each) where the kernel rounds
# dA and dR as product operands, read at 6.0e-3 at most (dq at the bound)
PULLBACK = {jnp.float32: 6e-5, jnp.bfloat16: 2.0 ** -6}


@pytest.mark.parametrize("t", [256, 1152], ids=["one_grid_step", "three_grid_steps"])
@pytest.mark.parametrize("gate", ["random", "bound"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("dv", [32, 64], ids=["v32", "v64"])
def test_the_chunks_bwd_kernel_pulls_the_parts_back(t, gate, dtype, dv):
    q, k, v, g, beta, _ = _inputs(t=t, gate=gate, dv=dv)
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    parts, pullback = jax.vjp(functools.partial(K._parts, scale=0.2), q, k, v, g, beta)
    keys = jax.random.split(jax.random.PRNGKey(7), len(parts))
    d_parts = tuple(jax.random.normal(key, p.shape).astype(p.dtype) for key, p in zip(keys, parts))
    refs = pullback(d_parts)
    *got_parts, inverse = K._chunk_parts(q, k, v, g, beta, 0.2, True, inverse=True)
    got = K._chunk_grads(q, k, v, g, beta, inverse, got_parts[2], got_parts[3], d_parts, 0.2, True)
    for a, ref, name in zip(got, refs, ("q", "k", "v", "g", "beta")):
        assert a.shape == ref.shape and a.dtype == ref.dtype, name
        ref = np.asarray(ref, np.float32)
        scale = float(np.max(np.abs(ref)))
        if name == "g":
            scale, tol = max(scale, float(jnp.max(jnp.abs(refs[1].astype(jnp.float32))))), GATE[dtype]
        else:
            tol = PULLBACK[dtype]
        np.testing.assert_allclose(np.asarray(a, np.float32), ref, atol=tol * scale, err_msg=f"d{name}")


def test_a_sequence_of_no_whole_chunks_is_padded_at_its_end():
    q, k, v, g, beta, do = _inputs(t=100)
    o = K.kda(q, k, v, g, beta)
    assert o.shape == v.shape
    np.testing.assert_allclose(o, K.kda_recurrence(q, k, v, g, beta), atol=2e-5)


def test_the_kernels_are_named_for_the_trace():
    q, k, v, g, beta, do = _inputs(t=128)
    text = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(K.kda(*a) * do),
                                       argnums=(0, 1, 2, 3, 4)))(q, k, v, g, beta))
    for name in ("kda_chunks", "kda_fwd", "kda_bwd_states", "kda_bwd_grads", "kda_chunks_bwd"):
        assert name in text
    assert "triangular_solve" not in text  # the pullback reads the inverse kda_chunks wrote


def test_no_decay_is_exponentiated_across_more_than_a_sub_chunk():
    """At the bound, e^{5 * 63} would overflow: a whole chunk's sum of g is
    never exponentiated with a positive sign, so every part is finite."""
    q, k, v, g, beta, _ = _inputs(t=128, gate="bound")
    parts = K._within_chunks(*(K._to_chunks(x) for x in (q, k, v, g, beta)), 0.2)
    for part in parts:
        assert np.all(np.isfinite(np.asarray(part, np.float32)))
    assert float(jnp.max(jnp.abs(parts[4]))) < 1e6  # M: e^{G_t - G_s} <= 1 where it is kept


@pytest.mark.parametrize("t", [128, 100], ids=["whole_chunks", "padded"])
def test_heads_taken_a_pass_at_a_time_give_what_all_at_once_give(monkeypatch, t):
    """Four heads in passes of one, then of two: the same output and gradients
    as one pass of four, to float32's rounding (each head's arithmetic is the
    same; only the programs' shapes differ)."""
    q, k, v, g, beta, do = _inputs(t=t, h=4)

    def run():
        o, pullback = jax.vjp(K.kda, q, k, v, g, beta)
        return (o,) + pullback(do)

    whole = run()
    for heads in (1, 2):
        monkeypatch.setattr(K, "HEADS_A_PASS", heads)
        for got, want in zip(run(), whole):
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
