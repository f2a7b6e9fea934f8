"""Flash attention — fused Pallas TPU kernels for the unsharded-sequence path.

The hot op of the transformer trial runtime (katib_tpu.models.transformer).
The reference has no kernel code at all (its trials delegate to
PyTorch/TF images — SURVEY.md §2.8/§2.9); on TPU the idiomatic equivalent is
a Pallas kernel that keeps the O(T^2) score matrix out of HBM entirely:
Q/K/V blocks stream HBM→VMEM, scores live only as a [block_q, block_k] VMEM
tile feeding the MXU, and the online-softmax recurrence

    m' = max(m, rowmax(S));  l' = l·e^{m−m'} + rowsum(e^{S−m'})
    acc' = acc·e^{m−m'} + e^{S−m'}·V

accumulates the output in fp32 scratch. The backward pass is the standard
two-kernel recomputation (dQ with KV innermost; dK/dV with Q innermost) from
the saved logsumexp — no attention matrix is ever materialized in either
direction. A causal band (``window=``) has kernels of its own, with no grid
axis over the keys and no scratch: a grid step holds all the keys its rows
can see ("Windowed kernels" below).

Sequence-sharded attention is handled by katib_tpu.ops.ring_attention (the
ring schedule rotates K/V between devices); this kernel is the within-device
fast path and the two compose: ring for cross-device blocks, flash for the
local block compute.

Falls back to interpret mode off-TPU (CPU tests) and to dense attention for
shapes the tiling cannot cover (tiny or non-divisible sequence lengths).
"""

from __future__ import annotations

import functools
import math
import operator
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30
_LANES = 128  # TPU lane width; scratch vectors are padded to this


def _dot_precision(dtype):
    """f32 blocks need HIGHEST precision or the MXU's bf16 decomposition
    drops ~3 decimal digits; bf16 blocks run at native MXU rate regardless."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


@functools.lru_cache(maxsize=1)
def _on_tpu() -> bool:
    """Asked of the backend once, plainly. An error here is an error: a
    caller handed ``False`` on a TPU host would run dense attention in the
    kernel's place and nobody would see it."""
    from ..utils.backend import require_devices

    return require_devices()[0].platform == "tpu"


def _use_kernel(interpret: Optional[bool]) -> bool:
    """Three-state kernel dispatch shared by every flash entry point:
    ``True`` forces the Pallas path (interpret mode off-TPU — kernel tests),
    ``False`` forces the dense fallback, ``None`` auto-selects by backend
    (interpret-mode Pallas off-TPU is orders of magnitude slower than one
    fused XLA attention)."""
    return interpret is True or (interpret is not False and _on_tpu())


# ---------------------------------------------------------------------------
# Grouped KV heads and the window
# ---------------------------------------------------------------------------
#
# Grouped heads: q is [B*H, T, D], k and v [B*KV, T, D] with H = KV * group;
# query row ``b`` of the flattened batch reads KV row ``b // group``. The dk/dv
# kernel walks the group's query heads in its innermost grid axis and sums.
#
# Window: key ``s`` is visible to query ``t`` iff ``0 <= t - s < window``. The
# windowed kernels (further down, under names of their own) have no grid axis
# over the band: one grid step holds the whole band of its outer block, every
# block of it an operand of its own, and computes only the sub-tiles the band
# touches. With ``window=None`` every index map and kernel body up to there is
# the plain causal one, over a group of query heads where ``group > 1``.

def _mask_scores(s, q0, k0, causal):
    """Scores of the block at rows ``q0``.., columns ``k0``.. with the keys a
    query may not see set to NEG_INF."""
    if not causal:
        return s
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(k_pos > q_pos, NEG_INF, s)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, causal: bool, sm_scale: float, block_q: int, block_k: int,
                kv_steps: int):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # Causal: skip blocks strictly above the diagonal.
    run = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(run)
    def _step():
        # dots stay in the input dtype (MXU does bf16 x bf16 -> f32 natively;
        # casting blocks to f32 first runs the MXU at the much slower f32
        # rate) — only the softmax recurrence is f32. f32 inputs request
        # HIGHEST precision so the MXU's bf16 decomposition keeps f32 fidelity.
        q = q_ref[0]                                # [bq, d]
        k = k_ref[0]                                # [bk, d]
        v = v_ref[0]                                # [bk, d]
        prec = _dot_precision(q.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=prec,
        ) * sm_scale                                # [bq, bk] f32
        s = _mask_scores(s, qi * block_q, ki * block_k, causal)

        m_prev = m_ref[:, 0:1]                      # [bq, 1]
        l_prev = l_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                      # [bq, bk] f32
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(pl.program_id(2) == kv_steps - 1)
    def _finish():
        l = l_ref[:, 0:1]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:, 0:1] + jnp.log(jnp.maximum(l, 1e-30))


def _kv_index_map(group):
    """Index map of a k/v block for the grids whose last axis walks kv blocks
    (forward, dq): grid ``(b, q block, kv block)``."""
    if group == 1:
        return lambda b, i, j: (b, j, 0)
    return lambda b, i, j: (b // group, j, 0)


def _fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret, window=None, group=1):
    """q: [B*H, T, D], k/v: [B*KV, T, D] -> (o [B*H, T, D], lse [B*H, T, 1])."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if window is not None:
        return _window_fwd(q, k, v, sm_scale, block_q, block_k, interpret, window, group)
    bh, t, d = q.shape
    kv_steps, kv_index = t // block_k, _kv_index_map(group)
    grid = (bh, t // block_q, kv_steps)
    kernel = functools.partial(
        _fwd_kernel, causal=causal, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, kv_steps=kv_steps,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)


# ---------------------------------------------------------------------------
# Backward kernels (recompute from saved logsumexp)
# ---------------------------------------------------------------------------

def _recompute_p_ds(q, k, v, do, lse, delta, mask, sm_scale):
    """Shared bwd block math: p [bq,bk] and ds [bq,bk] (pre-scaled, f32);
    ``mask`` hides the scores a query may not see.

    Dots take the blocks in their native dtype (bf16 MXU rate) and accumulate
    f32; only the elementwise recurrence is f32.
    """
    prec = _dot_precision(q.dtype)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=prec,
    ) * sm_scale
    s = mask(s)
    p = jnp.exp(s - lse)                            # lse [bq, 1] broadcasts
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=prec,
    )                                               # [bq, bk]
    ds = p * (dp - delta) * sm_scale                # delta [bq, 1]
    return p, ds


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, causal, sm_scale, block_q, block_k, kv_steps):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        _, ds = _recompute_p_ds(
            q, k, v, do, lse_ref[0], delta_ref[0],
            lambda s: _mask_scores(s, qi * block_q, ki * block_k, causal), sm_scale,
        )
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_dot_precision(k.dtype),
        )

    @pl.when(pl.program_id(2) == kv_steps - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, causal, sm_scale, block_q, block_k, q_steps, group=1):
    """One kv block of one KV head: the innermost grid axis walks the q
    blocks, of every query head of the group in turn."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    step = pl.program_id(2)
    qi = step if group == 1 else step % q_steps

    @pl.when(step == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        p, ds = _recompute_p_ds(
            q, k, v, do, lse_ref[0], delta_ref[0],
            lambda s: _mask_scores(s, qi * block_q, ki * block_k, causal), sm_scale,
        )
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_dot_precision(do.dtype),
        )
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_dot_precision(q.dtype),
        )

    @pl.when(step == group * q_steps - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(q, k, v, o, lse, do, causal, sm_scale, block_q, block_k, interpret,
         window=None, group=1):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t, d = q.shape
    bkv = k.shape[0]
    delta = jnp.sum(
        o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1, keepdims=True
    )  # [BH, T, 1]

    if window is not None:
        return _window_bwd(q, k, v, do, lse, delta, sm_scale, block_q, block_k,
                           interpret, window, group)

    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    row_spec = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
    kv_steps = t // block_k
    kv_spec_dq = pl.BlockSpec((1, block_k, d), _kv_index_map(group))

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, kv_steps=kv_steps,
        ),
        grid=(bh, t // block_q, kv_steps),
        in_specs=[q_spec, kv_spec_dq, kv_spec_dq, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)

    # dk/dv: grid iterates q blocks innermost for a fixed kv block (of every
    # query head of the group: the sum over the group is this kernel's).
    q_steps = t // block_q
    if group == 1:
        def q_index(b, i, j):
            return (b, j, 0)
    else:
        def q_index(b, i, j):
            return (b * group + j // q_steps, j % q_steps, 0)

    q_spec2 = pl.BlockSpec((1, block_q, d), q_index)
    kv_spec2 = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0))
    row_spec2 = pl.BlockSpec((1, block_q, 1), q_index)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, q_steps=q_steps, group=group,
        ),
        grid=(bkv, t // block_k, group * q_steps),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2, row_spec2],
        out_specs=[kv_spec2, kv_spec2],
        out_shape=[
            jax.ShapeDtypeStruct((bkv, t, d), k.dtype),
            jax.ShapeDtypeStruct((bkv, t, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Windowed kernels: one grid step holds its block's whole band
# ---------------------------------------------------------------------------
#
# Forward and dq: grid ``(b, q block)``. The kv blocks a q block's band can touch
# are so many operands of the step (the same array under one BlockSpec each),
# the last of them the block that holds the q block's last row; one that would
# lie before the sequence is clamped to block 0 and hidden by position. dk/dv
# the other way round: grid ``(b, kv block, query head of the group)``, the q, do,
# lse and delta blocks that see the kv block as operands, the first of them the
# block that holds the kv block's first key; one past the sequence's end is
# clamped to the last block and hidden. Each block is fetched once a step.
#
# Inside a step the outer block is walked in chunks of WINDOW_CHUNK rows (keys,
# for dk/dv), unrolled at trace time, each in one pass over its scores: the
# forward keeps its running maximum, sum and output in values, from one kv block
# to the next of a chunk, and writes each row once; no scratch, no first or
# last step. Where the outer block is a whole number of inner blocks, the operands lie
# at static offsets from a chunk, and a chunk takes static slices of them: the
# aligned range its band touches and no more, cut where the band's two edges can
# fall, so that only the edge pieces are masked (``_band_plan``). Where it is not
# (unequal blocks that do not divide), a chunk takes every operand whole and
# masks by position.

# Rows (dk/dv: keys) of a block computed in one pass. At a window of 512 a chunk of 128 computes 640
# columns for the 512 its rows see, one of 256 computes 768. Measured on a v5e at the sparse cell's
# sliding layer (64 heads over 8, T 8192, heads of 128, window 512, bfloat16; ms a call forward /
# dq / dk/dv, q and kv blocks alike, PERF.md PR 36): the stepping grid 4.76 / 2.83 / 3.47; chunks of
# 128 in blocks of 512 2.00 / 1.88 / 2.56, of 1,024 1.75 / 1.70 / 2.34; chunks of 256 in blocks of
# 512 1.99 / 1.89 / 3.16, of 1,024 1.76 / 1.74 / 3.10.
WINDOW_CHUNK = 128


def _band_kv_blocks(t, window, block_q, block_k) -> int:
    """Most kv blocks a q block's band touches: keys qi*bq - window + 1 .. qi*bq + bq - 1."""
    return max(
        (qi * block_q + block_q - 1) // block_k - max(qi * block_q - (window - 1), 0) // block_k + 1
        for qi in range(t // block_q)
    )


def _band_q_blocks(t, window, block_q, block_k) -> int:
    """Most q blocks that see one kv block: queries ki*bk .. ki*bk + bk + window - 2."""
    last = t // block_q - 1
    return max(
        min((ki * block_k + block_k + window - 2) // block_q, last) - (ki * block_k) // block_q + 1
        for ki in range(t // block_k)
    )


def _band_plan(block_out, block_in, count, window, keys_outer):
    """The static walk of an outer block over its ``count`` operands of
    ``block_in``: for each chunk the pieces ``(operand, start, stop, gap0)`` to
    compute, ``gap0`` the distance from the piece's first key back to its first
    query. ``keys_outer``: the outer block holds keys and the operands the
    queries that see them (dk/dv); else queries and their keys. None where the
    operands' offsets from the block are not static."""
    if block_out % block_in:
        return None
    chunk = math.gcd(block_out, WINDOW_CHUNK)
    align = math.gcd(chunk, block_in)
    base = 0 if keys_outer else block_out - count * block_in  # operand 0, from the outer block's start
    plan = []
    for a0 in range(0, block_out, chunk):
        a1 = a0 + chunk
        if keys_outer:  # queries a0 .. a1 + window - 2 see a key of the chunk, a1 - 1 .. a0 + window - 1 all
            lo, hi, all_lo, all_hi = a0, a1 + window - 1, a1 - 1, a0 + window
        else:           # keys a0 - window + 1 .. a1 - 1 are seen by a row of the chunk, a1 - window .. a0 by all
            lo, hi, all_lo, all_hi = a0 - window + 1, a1, a1 - window, a0 + 1
        lo, all_hi = (x // align * align for x in (lo, all_hi))
        hi, all_lo = (-(-x // align) * align for x in (hi, all_lo))
        lo, hi = max(lo, base), min(hi, base + count * block_in)
        cuts = {lo, hi} | {base + j * block_in for j in range(count)}
        if all_lo < all_hi:
            cuts |= {all_lo, all_hi}
        cuts = sorted(c for c in cuts if lo <= c <= hi)
        pieces = []
        for start, stop in zip(cuts, cuts[1:]):
            j = (start - base) // block_in
            at = start - base - j * block_in
            pieces.append((j, at, at + stop - start, start - a0 if keys_outer else a0 - start))
        plan.append(pieces)
    return plan


def _mask_band(s, gap0, window, outside=None):
    """Scores whose entry ``[i, j]`` is of a key ``gap0 + i - j`` places behind
    its query, NEG_INF where that is not within ``0 .. window - 1`` or where
    ``outside`` (a row or a column of flags) says so. A static ``gap0`` drops
    the compare that no entry of the tile can meet, and the tile comes back as
    it is when none can."""
    rows, cols = s.shape
    static = isinstance(gap0, int)
    ahead = not static or gap0 - (cols - 1) < 0
    behind = not static or gap0 + rows - 1 >= window
    if not (ahead or behind):
        return s if outside is None else jnp.where(outside, NEG_INF, s)
    gap = gap0 + (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                  - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
    hidden = (gap < 0) | (gap >= window) if ahead and behind else gap < 0 if ahead else gap >= window
    return jnp.where(hidden if outside is None else hidden | outside, NEG_INF, s)


def _walk_band(body, plan, block_out, block_in, count, outer0, t, keys_outer):
    """Run ``body(a0, chunk, pieces)`` for every chunk of the outer block that
    starts at position ``outer0``; a piece is ``(operand, start, stop, gap0,
    outside)``."""
    from jax.experimental import pallas as pl

    chunk = math.gcd(block_out, WINDOW_CHUNK)
    # where operand 0 stands before it is clamped into the sequence: the block of the outer block's
    # first key (dk/dv), or count - 1 blocks before the block of its last row
    first0 = (outer0 // block_in if keys_outer
              else (outer0 + block_out - 1) // block_in - (count - 1)) * block_in

    def outside(j, lo, hi):
        shape, axis = ((hi - lo, 1), 0) if keys_outer else ((1, hi - lo), 1)
        pos = first0 + j * block_in + lo + jax.lax.broadcasted_iota(jnp.int32, shape, axis)
        return (pos < 0) | (pos >= t)

    def walk(pieces_of):
        for a0 in range(0, block_out, chunk):
            body(a0, chunk, pieces_of(a0))

    if plan is None:
        def whole(a0):
            gap = outer0 + a0 - first0
            return [(j, 0, block_in, j * block_in - gap if keys_outer else gap - j * block_in,
                     outside(j, 0, block_in)) for j in range(count)]

        walk(whole)
        return
    clamped = (first0 < 0) | (first0 + count * block_in > t)
    pl.when(clamped)(lambda: walk(
        lambda a0: [(j, lo, hi, gap0, outside(j, lo, hi)) for j, lo, hi, gap0 in plan[a0 // chunk]]))
    pl.when(jnp.logical_not(clamped))(lambda: walk(
        lambda a0: [piece + (None,) for piece in plan[a0 // chunk]]))


def _window_fwd_kernel(q_ref, *refs, sm_scale, block_q, block_k, window, count, plan, t):
    from jax.experimental import pallas as pl

    k_refs, v_refs, (o_ref, lse_ref) = refs[:count], refs[count:2 * count], refs[2 * count:]
    q0 = pl.program_id(1) * block_q
    prec = _dot_precision(q_ref.dtype)

    def rows_in_one_pass(a0, chunk, pieces):
        rows = slice(a0, a0 + chunk)
        q = q_ref[0, rows, :]
        scores = []
        for j, lo, hi, gap0, outside in pieces:
            s = jax.lax.dot_general(
                q, k_refs[j][0, lo:hi, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec,
            ) * sm_scale
            scores.append(_mask_band(s, gap0, window, outside))
        # The kv blocks in turn under a running maximum, held in values: a block's probabilities
        # are rounded relative to the maximum up to and with that block, as the stepping grid's
        # were (_window_kv_block says why that is kept). A row that sees nothing of a block takes
        # p = 1 there until a later block's alpha = exp(NEG_INF - m) = 0 wipes it out; every row
        # sees its own key, in the last block, so m ends as a score and l at least 1.
        m = l = acc = None
        for operand in sorted({piece[0] for piece in pieces}):
            mine = [(s, piece) for s, piece in zip(scores, pieces) if piece[0] == operand]
            m_new = functools.reduce(
                jnp.maximum, [jnp.max(s, axis=-1, keepdims=True) for s, _ in mine], *([] if m is None else [m]))
            ps = [jnp.exp(s - m_new) for s, _ in mine]
            l_new = functools.reduce(operator.add, [jnp.sum(p, axis=-1, keepdims=True) for p in ps])
            acc_new = functools.reduce(operator.add, [
                jax.lax.dot_general(
                    p.astype(o_ref.dtype), v_refs[j][0, lo:hi, :], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32, precision=prec,
                ) for p, (_, (j, lo, hi, _, _)) in zip(ps, mine)])
            if m is not None:
                alpha = jnp.exp(m - m_new)
                l_new, acc_new = l * alpha + l_new, acc * alpha + acc_new
            m, l, acc = m_new, l_new, acc_new
        o_ref[0, rows, :] = (acc / l).astype(o_ref.dtype)
        lse_ref[0, rows, :] = m + jnp.log(l)

    _walk_band(rows_in_one_pass, plan, block_q, block_k, count, q0, t, False)


def _window_dq_kernel(q_ref, do_ref, lse_ref, delta_ref, *refs,
                      sm_scale, block_q, block_k, window, count, plan, t):
    from jax.experimental import pallas as pl

    k_refs, v_refs, dq_ref = refs[:count], refs[count:2 * count], refs[2 * count]
    q0 = pl.program_id(1) * block_q

    def rows_in_one_pass(a0, chunk, pieces):
        rows = slice(a0, a0 + chunk)
        q, do, lse, delta = (ref[0, rows, :] for ref in (q_ref, do_ref, lse_ref, delta_ref))
        parts = []
        for j, lo, hi, gap0, outside in pieces:
            k, v = k_refs[j][0, lo:hi, :], v_refs[j][0, lo:hi, :]
            _, ds = _recompute_p_ds(
                q, k, v, do, lse, delta,
                functools.partial(_mask_band, gap0=gap0, window=window, outside=outside), sm_scale)
            parts.append(jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=_dot_precision(k.dtype),
            ))
        dq_ref[0, rows, :] = functools.reduce(operator.add, parts).astype(dq_ref.dtype)

    _walk_band(rows_in_one_pass, plan, block_q, block_k, count, q0, t, False)


def _window_dkv_kernel(k_ref, v_ref, *refs, sm_scale, block_q, block_k, window, count, plan, t, group):
    """One kv block under one query head of its group; dk and dv are summed
    over the group's heads in scratch and written at the last."""
    from jax.experimental import pallas as pl

    q_refs, do_refs, lse_refs, delta_refs = (refs[n * count:(n + 1) * count] for n in range(4))
    dk_ref, dv_ref, dk_acc, dv_acc = refs[4 * count:]
    k0 = pl.program_id(1) * block_k
    head = pl.program_id(2)

    @pl.when(head == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def keys_in_one_pass(a0, chunk, pieces):
        keys = slice(a0, a0 + chunk)
        k, v = k_ref[0, keys, :], v_ref[0, keys, :]
        dks, dvs = [], []
        for j, lo, hi, gap0, outside in pieces:
            q, do, lse, delta = (
                ref[j][0, lo:hi, :] for ref in (q_refs, do_refs, lse_refs, delta_refs))
            p, ds = _recompute_p_ds(
                q, k, v, do, lse, delta,
                functools.partial(_mask_band, gap0=gap0, window=window, outside=outside), sm_scale)
            dvs.append(jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=_dot_precision(do.dtype),
            ))
            dks.append(jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=_dot_precision(q.dtype),
            ))
        dk_acc[keys, :] += functools.reduce(operator.add, dks)
        dv_acc[keys, :] += functools.reduce(operator.add, dvs)

    _walk_band(keys_in_one_pass, plan, block_k, block_q, count, k0, t, True)

    @pl.when(head == group - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _band_kv_specs(t, d, block_q, block_k, window, group):
    """(count, one BlockSpec a kv block of a q block's band) for grid ``(b, q block)``."""
    from jax.experimental import pallas as pl

    count = _band_kv_blocks(t, window, block_q, block_k)

    def kv_index(b, i, *, j):
        return (b // group, jnp.maximum((i * block_q + block_q - 1) // block_k - (count - 1) + j, 0), 0)

    return count, [pl.BlockSpec((1, block_k, d), functools.partial(kv_index, j=j)) for j in range(count)]


def _window_fwd(q, k, v, sm_scale, block_q, block_k, interpret, window, group):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t, d = q.shape
    count, kv_specs = _band_kv_specs(t, d, block_q, block_k, window, group)
    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0))
    return pl.pallas_call(
        functools.partial(
            _window_fwd_kernel, sm_scale=sm_scale, block_q=block_q, block_k=block_k, window=window,
            count=count, plan=_band_plan(block_q, block_k, count, window, False), t=t,
        ),
        grid=(bh, t // block_q),
        in_specs=[q_spec] + kv_specs + kv_specs,
        out_specs=[q_spec, pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="flash_window_fwd",
    )(q, *[k] * count, *[v] * count)


def _window_bwd(q, k, v, do, lse, delta, sm_scale, block_q, block_k, interpret, window, group):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t, d = q.shape
    bkv = k.shape[0]
    count, kv_specs = _band_kv_specs(t, d, block_q, block_k, window, group)
    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0))
    row_spec = pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(
            _window_dq_kernel, sm_scale=sm_scale, block_q=block_q, block_k=block_k, window=window,
            count=count, plan=_band_plan(block_q, block_k, count, window, False), t=t,
        ),
        grid=(bh, t // block_q),
        in_specs=[q_spec, q_spec, row_spec, row_spec] + kv_specs + kv_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="flash_window_bwd_dq",
    )(q, do, lse, delta, *[k] * count, *[v] * count)

    if block_q % block_k == 0:  # dk/dv's walk is static only over kv blocks of whole q blocks
        block_k = block_q
    count = _band_q_blocks(t, window, block_q, block_k)
    last = t // block_q - 1

    def q_index(b, i, g, *, j):
        return (b * group + g, jnp.minimum(i * block_k // block_q + j, last), 0)

    q_specs = [pl.BlockSpec((1, block_q, d), functools.partial(q_index, j=j)) for j in range(count)]
    row_specs = [pl.BlockSpec((1, block_q, 1), functools.partial(q_index, j=j)) for j in range(count)]
    kv_spec = pl.BlockSpec((1, block_k, d), lambda b, i, g: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _window_dkv_kernel, sm_scale=sm_scale, block_q=block_q, block_k=block_k, window=window,
            count=count, plan=_band_plan(block_k, block_q, count, window, True), t=t, group=group,
        ),
        grid=(bkv, t // block_k, group),
        in_specs=[kv_spec, kv_spec] + q_specs + q_specs + row_specs + row_specs,
        out_specs=[kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bkv, t, d), k.dtype),
            jax.ShapeDtypeStruct((bkv, t, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_window_bwd_dkv",
    )(k, v, *[q] * count, *[do] * count, *[lse] * count, *[delta] * count)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper on [BH, T, D]
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_bhtd(q, k, v, causal, sm_scale, block_q, block_k, interpret, window=None, group=1):
    o, _ = _fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret, window, group)
    return o


def _flash_bhtd_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret, window, group):
    o, lse = _fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret, window, group)
    return o, (q, k, v, o, lse)


def _flash_bhtd_bwd(causal, sm_scale, block_q, block_k, interpret, window, group, res, do):
    q, k, v, o, lse = res
    # The backward kernels prefer symmetric MXU-sized tiles: measured on v5e
    # (T=2048 d=64 causal), fwd+bwd with the forward's asymmetric bq=512
    # runs 10% SLOWER than bq=bk=1024 despite the faster forward — so bwd
    # blocks are chosen independently of the forward's (BWD_BLOCK_CAP). A
    # windowed call keeps the forward's: _window_block sized them for the
    # dk/dv step, the widest of the three.
    t = q.shape[1]
    bwd_block = None if window is not None else _auto_block(t, BWD_BLOCK_CAP)
    bq = bwd_block or block_q
    bk = bwd_block or block_k
    dq, dk, dv = _bwd(q, k, v, o, lse, do, causal, sm_scale, bq, bk,
                      interpret, window, group)
    return dq, dk, dv


_flash_bhtd.defvjp(_flash_bhtd_fwd, _flash_bhtd_bwd)


def _auto_block(t: int, cap: int) -> Optional[int]:
    """Largest multiple of 128 that divides t, capped — big blocks keep the
    MXU busy (measured on v5e at T=2048 d=64: 1024-blocks are 5.6x faster
    than 128-blocks and 2.3x faster than XLA dense attention). None when no
    lane-aligned tiling exists (caller falls back to dense)."""
    for b in range(min(cap, t) // 128 * 128, 127, -128):
        if t % b == 0:
            return b
    return None


FWD_BLOCK_Q_CAP = 512   # measured v5e sweep (T=2048 d=64 causal): bq=512/
FWD_BLOCK_K_CAP = 1024  # bk=1024 runs 1.6x faster than symmetric 1024 blocks
                        # (0.47ms vs 0.74ms) and is never worse at T=1024/4096;
                        # the smaller Q tile pipelines better against the
                        # K-innermost grid while K blocks stay MXU-sized
BWD_BLOCK_CAP = 1024    # backward tiles stay symmetric/large (see
                        # _flash_bhtd_bwd: small Q tiles regress fwd+bwd 10%)


def _dense_grouped(q, k, v, causal, window, sm_scale):
    """Masked dense attention for what ring_attention.dense_attention does not
    take: grouped KV heads and a window. [B, T, H, D] against [B, T, KV, D]."""
    b, t, h, d = q.shape
    group = h // k.shape[2]
    qg = q.reshape(b, t, k.shape[2], group, d)
    prec = _dot_precision(q.dtype)
    s = jnp.einsum("bqcgd,bkcd->bcgqk", qg, k, precision=prec,
                   preferred_element_type=jnp.float32) * sm_scale
    pos = jnp.arange(t)
    gap = pos[:, None] - pos[None, :]
    seen = jnp.ones((t, t), bool)
    if causal:
        seen = seen & (gap >= 0)
    if window is not None:
        seen = seen & (gap < window)
    p = jax.nn.softmax(jnp.where(seen, s, NEG_INF), axis=-1)
    o = jnp.einsum("bcgqk,bkcd->bqcgd", p.astype(v.dtype), v, precision=prec)
    return o.reshape(b, t, h, d).astype(q.dtype)


WINDOW_BLOCK_CAP = 1024  # windowed calls, all three kernels: a chunk computes the same columns in any block,
                         # and a block of 1,024 halves the grid steps of one of 512 (WINDOW_CHUNK's note)
WINDOW_VMEM_BUDGET = 12 * 2**20  # for a step's operands, of the 16 MiB a kernel may scope on a v5e
WINDOW_KV_BLOCK = 512  # forward and dq take the band in kv blocks of so many keys (_window_kv_block)


def _window_block(t: int, window: int, d: int, itemsize: int) -> Optional[int]:
    """The q block of a windowed call (and dk/dv's kv block): the largest
    multiple of 128 that divides t, is no wider than WINDOW_BLOCK_CAP, and
    lets the widest step fit WINDOW_VMEM_BUDGET. That step is dk/dv's: the
    band's q, do, lse and delta blocks (a row statistic's block is padded to
    128 lanes) beside k, v, dk and dv, all double-buffered, and two float32
    accumulators. None where no block fits — at heads of 128 in bfloat16 a
    window of some 3,900 keys — and the caller computes dense."""
    for b in range(min(WINDOW_BLOCK_CAP, t) // 128 * 128, 127, -128):
        if t % b:
            continue
        band = _band_q_blocks(t, window, b, b) * 2 * b * (d * itemsize + _LANES * 4)
        if 2 * (band + 4 * b * d * itemsize) + 2 * b * d * 4 <= WINDOW_VMEM_BUDGET:
            return b
    return None


def _window_kv_block(block: int) -> int:
    """The kv block of a windowed call whose q block is ``block``: WINDOW_KV_BLOCK
    where that divides it. The forward rounds a kv block's probabilities to the
    operands' type relative to the running maximum up to that block, so the
    block's width is part of the result. At 512 the forward's ``o`` and ``lse``
    are the stepping kernels' in all but 2 of 10,000 elements, and the sparse
    cell's loss after three steps at a rate of 1.13e-3, where the trial is on
    the edge of diverging, stays 1.0e-2 from the float32 reference's; one
    maximum over all a chunk's keys reads 1.8e-2 there and the running maximum
    over blocks of 1,024 keys 2.05e-2, for the same time a call (PERF.md,
    PR 36). dk/dv, whose walk is static only over kv blocks of whole q blocks,
    takes kv blocks as wide as the q block (_window_bwd)."""
    return WINDOW_KV_BLOCK if block % WINDOW_KV_BLOCK == 0 else block


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Fused attention on [B, T, H, D] (same layout as ring/dense attention).

    ``k`` and ``v`` may carry fewer heads, [B, T, KV, D] with H a multiple of
    KV: query head ``j`` reads KV head ``j // (H // KV)``. ``window`` (with
    ``causal``) hides every key more than ``window - 1`` positions behind its
    query. A windowed call runs kernels of its own ("Windowed kernels" above):
    a grid step holds a block's whole band and computes, a chunk of
    WINDOW_CHUNK rows at a time, the aligned columns that chunk's band
    touches — 640 for the 512 its rows see at a window of 512, where the
    stepping grid this replaced computed 1,024. Its q blocks are
    ``_window_block``'s, forward and backward, its kv blocks
    ``_window_kv_block``'s; a band too wide for a step's VMEM (some 3,900
    keys at bfloat16 heads of 128) is computed dense.

    Differentiable (custom VJP, recompute-based backward). Forward block
    sizes default to the largest dividing multiple of 128, asymmetric
    bq<=FWD_BLOCK_Q_CAP (512) / bk<=FWD_BLOCK_K_CAP (1024) per the measured
    v5e sweep; the backward kernels pick their own symmetric <=1024 tiles
    regardless of block_q/block_k (see _flash_bhtd_bwd). Sequences the
    tiling cannot cover (T < 2 MXU rows or not a multiple of 128) fall back
    to dense attention — semantics are identical.
    """
    from .ring_attention import dense_attention

    b, t, h, d = q.shape
    kv_heads = k.shape[2]
    if h % kv_heads or v.shape[2] != kv_heads:
        raise ValueError(f"{h} query heads cannot share {kv_heads} key/value heads")
    group = h // kv_heads
    if window is not None:
        if not causal or window < 1:
            raise ValueError("a window is a causal band of at least one key")
        if window >= t:
            window = None  # the band is the causal half
    if window is None:
        auto_q, auto_k = _auto_block(t, FWD_BLOCK_Q_CAP), _auto_block(t, FWD_BLOCK_K_CAP)
    else:
        auto_q = _window_block(t, window, d, q.dtype.itemsize)
        auto_k = auto_q and _window_kv_block(auto_q)
    block_q = min(block_q, t) if block_q else (auto_q or t + 1)
    block_k = min(block_k, t) if block_k else (auto_k or t + 1)

    def dense_fallback():
        if group > 1 or window is not None:
            return _dense_grouped(q, k, v, causal, window,
                                  1.0 / math.sqrt(d) if sm_scale is None else sm_scale)
        # dense_attention hard-codes 1/sqrt(d); fold a custom sm_scale into q
        # so fallback results match the kernel on every platform
        qs = q if sm_scale is None else q * (sm_scale * math.sqrt(d))
        return dense_attention(qs, k, v, causal=causal)

    if t % block_q or t % block_k or t < 16 or not _use_kernel(interpret):
        return dense_fallback()
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)

    def to_bhtd(x):
        return x.transpose(0, 2, 1, 3).reshape(b * x.shape[2], t, d)

    o = _flash_bhtd(
        to_bhtd(q), to_bhtd(k), to_bhtd(v),
        causal, float(sm_scale), block_q, block_k, bool(interpret), window, group,
    )
    return o.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def flash_attention_with_lse(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Like flash_attention but also returns the per-row logsumexp
    ([B, T, H], f32) so partial attentions over different K/V blocks can be
    merged exactly — the primitive ring attention builds on (each ring step
    attends the local Q against one rotating K/V block, then folds the
    normalized block output into the running result via the lse weights).

    Differentiation note: the merge path re-derives gradients through the
    *fallback* expression; the Pallas fast path is forward-only here, so
    callers that need gradients under jit on TPU go through the dense
    fallback math (ring attention's callers differentiate the merged
    expression, which XLA fuses per block anyway).
    """
    b, t, h, d = q.shape
    tk = k.shape[1]
    if causal and tk != t:
        raise ValueError(
            f"causal flash_attention_with_lse needs equal q/k lengths, got {t} vs {tk}"
        )
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)

    use_kernel = False
    bq = min(block_q, t) if block_q else _auto_block(t, FWD_BLOCK_Q_CAP)
    bk = min(block_k, tk) if block_k else _auto_block(tk, FWD_BLOCK_K_CAP)
    if (
        tk == t  # the kernel grid assumes equal q/kv lengths
        and bq and bk and t % bq == 0 and tk % bk == 0 and t >= 16
    ):
        use_kernel = _use_kernel(interpret)

    if use_kernel:
        def to_bhtd(x):
            return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

        o, lse = _fwd(
            to_bhtd(q), to_bhtd(k), to_bhtd(v), causal, float(sm_scale),
            bq, bk, bool(interpret),
        )
        o = o.reshape(b, h, t, d).transpose(0, 2, 1, 3)
        lse = lse.reshape(b, h, t).transpose(0, 2, 1)  # [B, T, H]
        return o, lse

    # dense fallback with explicit lse (differentiable everywhere); f32 dots
    # request HIGHEST so the TPU MXU decomposition keeps f32 fidelity
    s = jnp.einsum(
        "bqhd,bkhd->bhqk",
        q.astype(jnp.float32),
        k.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ) * sm_scale
    if causal:
        mask = jnp.tril(jnp.ones((t, t), dtype=bool))
        s = jnp.where(mask[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum(
        "bhqk,bkhd->bqhd", (p / l).astype(q.dtype), v,
        precision=jax.lax.Precision.HIGHEST,
    )
    lse = (m + jnp.log(l))[..., 0].transpose(0, 2, 1)  # [B, T, H]
    return o, lse


def flash_block_grads(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    o: jnp.ndarray,
    lse: jnp.ndarray,
    do: jnp.ndarray,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-block gradients against a GLOBAL logsumexp: with p = exp(s - lse)
    every K/V block's (dq, dk, dv) contribution is independent, so ring
    attention's backward can call this once per rotation. Layout
    [B, T, H, D]; lse [B, T, H] f32. Uses the Pallas _bwd kernels on TPU
    (scores never materialize), dense f32 math elsewhere. ``interpret=True``
    forces the kernel path in Pallas interpret mode (CI coverage of the ring
    backward's kernel glue off-TPU); ``interpret=False`` forces the dense
    fallback."""
    b, t, h, d = q.shape
    if k.shape[1] != t:
        raise ValueError("flash_block_grads needs equal q/k block lengths")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)

    bq = _auto_block(t, 1024)
    use_kernel = bq and t % bq == 0 and t >= 16 and _use_kernel(interpret)
    if use_kernel:
        def to_bhtd(x):
            return x.transpose(0, 2, 1, 3).reshape(b * h, t, x.shape[-1])

        lse_bhtd = lse.transpose(0, 2, 1).reshape(b * h, t, 1)
        dq, dk, dv = _bwd(
            to_bhtd(q), to_bhtd(k), to_bhtd(v), to_bhtd(o), lse_bhtd,
            to_bhtd(do), causal, float(sm_scale), bq, bq, bool(interpret),
        )
        back = lambda x: x.reshape(b, h, t, d).transpose(0, 2, 1, 3)
        return back(dq), back(dk), back(dv)

    prec = jax.lax.Precision.HIGHEST
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    dof = do.astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf, precision=prec) * sm_scale
    if causal:
        mask = jnp.tril(jnp.ones((t, t), dtype=bool))
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jnp.exp(s - lse.transpose(0, 2, 1)[..., None])
    delta = jnp.sum(dof * o.astype(jnp.float32), axis=-1)          # [B, T, H]
    dp = jnp.einsum("bqhd,bkhd->bhqk", dof, vf, precision=prec)
    ds = p * (dp - delta.transpose(0, 2, 1)[..., None]) * sm_scale
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, kf, precision=prec)
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, qf, precision=prec)
    dv = jnp.einsum("bhqk,bqhd->bkhd", p, dof, precision=prec)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def merge_attention_blocks(
    o1: jnp.ndarray, lse1: jnp.ndarray, o2: jnp.ndarray, lse2: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fold two normalized partial attentions (over disjoint K/V blocks) into
    one: o = softmax-weighted combination, lse = log(e^lse1 + e^lse2).
    o: [B, T, H, D]; lse: [B, T, H] f32. Fully-masked partials carry
    lse = NEG_INF (finite −1e30, not −inf) and drop out exactly."""
    m = jnp.maximum(lse1, lse2)
    both_masked = m <= NEG_INF  # masked lse is the FINITE sentinel NEG_INF
    m_safe = jnp.where(both_masked, 0.0, m)  # avoid exp(-1e30 - -1e30) = 1 drift
    w1 = jnp.exp(lse1 - m_safe)
    w2 = jnp.exp(lse2 - m_safe)
    denom = jnp.maximum(w1 + w2, 1e-30)
    o = (
        o1.astype(jnp.float32) * (w1 / denom)[..., None]
        + o2.astype(jnp.float32) * (w2 / denom)[..., None]
    ).astype(o1.dtype)
    lse = m_safe + jnp.log(denom)
    lse = jnp.where(both_masked, NEG_INF, lse)
    return o, lse


def sharded_flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh,
    causal: bool = False,
    batch_axes: Tuple[str, ...] = ("data", "fsdp"),
    head_axis: str = "model",
    **kw,
) -> jnp.ndarray:
    """shard_map wrapper for the seq-unsharded case: batch over data/fsdp,
    heads over model — each device runs the flash kernel on its local heads
    with no collectives (heads are independent)."""
    from jax.sharding import PartitionSpec as P

    shard_map = jax.shard_map

    from ..parallel.mesh import mesh_axis_sizes

    sizes = mesh_axis_sizes(mesh)
    b, _, h, _ = q.shape
    # Shard only over axes the actual shape divides; anything else computes
    # replicated on those devices (correct, just redundant).
    from ..parallel.mesh import activation_batch_axes

    batch = activation_batch_axes(sizes, b, batch_axes) or None
    head_size = sizes.get(head_axis, 1)
    head = head_axis if head_size > 1 and h % head_size == 0 else None
    spec = P(batch, None, head, None)
    fn = shard_map(
        functools.partial(flash_attention, causal=causal, **kw),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,  # pallas_call outputs carry no vma annotation
    )
    return fn(q, k, v)
