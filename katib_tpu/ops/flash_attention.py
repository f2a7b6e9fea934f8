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
direction.

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
# innermost grid axis then runs over the blocks of the band only: its length is
# the most blocks any outer block's band touches, step ``j`` is block
# ``first(outer) + j``, and a step past the band's end is skipped (its block
# index is clamped, so nothing is fetched for it). Blocks wholly outside the
# band are never computed. With ``window=None`` and ``group == 1`` every index
# map and kernel body below is the plain causal one.

def _first_kv_block(qi, window, block_q, block_k):
    """First kv block that the band of q block ``qi`` touches."""
    return jnp.maximum(qi * block_q - (window - 1), 0) // block_k


def _band_kv_steps(t, window, block_q, block_k) -> int:
    """Most kv blocks a q block's band touches: keys qi*bq - window + 1 .. qi*bq + bq - 1."""
    return max(
        (qi * block_q + block_q - 1) // block_k - max(qi * block_q - (window - 1), 0) // block_k + 1
        for qi in range(t // block_q)
    )


def _first_q_block(ki, block_q, block_k):
    """First q block that sees kv block ``ki`` (causal: the one holding its first key)."""
    return (ki * block_k) // block_q


def _band_q_steps(t, window, block_q, block_k) -> int:
    """Most q blocks that see one kv block: queries ki*bk .. ki*bk + bk + window - 2."""
    last = t // block_q - 1
    return max(
        min((ki * block_k + block_k + window - 2) // block_q, last) - (ki * block_k) // block_q + 1
        for ki in range(t // block_k)
    )


def _mask_scores(s, q0, k0, causal, window):
    """Scores of the block at rows ``q0``.., columns ``k0``.. with the keys a
    query may not see set to NEG_INF."""
    if not causal and window is None:
        return s
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    hidden = k_pos > q_pos
    if window is not None:
        hidden = hidden | (q_pos - k_pos >= window)
    return jnp.where(hidden, NEG_INF, s)


def _kernel_name(base: str, window) -> str:
    """Windowed calls carry their own names, so a trace tells them apart."""
    return base if window is None else base.replace("flash_", "flash_window_", 1)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, causal: bool, sm_scale: float, block_q: int, block_k: int,
                kv_steps: int, window: Optional[int] = None):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    if window is not None:  # the grid's last axis walks the band's blocks
        ki = _first_kv_block(qi, window, block_q, block_k) + ki

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # Causal: skip blocks strictly above the diagonal (in a band: past its end).
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
        s = _mask_scores(s, qi * block_q, ki * block_k, causal, window)

        # a row whose keys in this block are all hidden (a band's first block)
        # accumulates p = 1 here; the block that holds its diagonal follows,
        # and alpha = exp(NEG_INF - m) = 0 wipes that out
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


def _kv_index_maps(t, block_q, block_k, window, group):
    """(kv_steps, index map of a k/v block) for the grids whose last axis
    walks kv blocks (forward, dq): grid ``(b, q block, step)``."""
    if window is None:
        if group == 1:
            return t // block_k, lambda b, i, j: (b, j, 0)
        return t // block_k, lambda b, i, j: (b // group, j, 0)
    last = t // block_k - 1

    def kv_index(b, i, j):
        return (b // group, jnp.minimum(_first_kv_block(i, window, block_q, block_k) + j, last), 0)

    return _band_kv_steps(t, window, block_q, block_k), kv_index


def _fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret, window=None, group=1):
    """q: [B*H, T, D], k/v: [B*KV, T, D] -> (o [B*H, T, D], lse [B*H, T, 1])."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t, d = q.shape
    kv_steps, kv_index = _kv_index_maps(t, block_q, block_k, window, group)
    grid = (bh, t // block_q, kv_steps)
    kernel = functools.partial(
        _fwd_kernel, causal=causal, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, kv_steps=kv_steps, window=window,
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
        name=_kernel_name("flash_fwd", window),
    )(q, k, v)


# ---------------------------------------------------------------------------
# Backward kernels (recompute from saved logsumexp)
# ---------------------------------------------------------------------------

def _recompute_p_ds(q, k, v, do, lse, delta, qi, ki, causal, sm_scale,
                    block_q, block_k, window=None):
    """Shared bwd block math: p [bq,bk] and ds [bq,bk] (pre-scaled, f32).

    Dots take the blocks in their native dtype (bf16 MXU rate) and accumulate
    f32; only the elementwise recurrence is f32.
    """
    prec = _dot_precision(q.dtype)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=prec,
    ) * sm_scale
    s = _mask_scores(s, qi * block_q, ki * block_k, causal, window)
    p = jnp.exp(s - lse)                            # lse [bq, 1] broadcasts
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=prec,
    )                                               # [bq, bk]
    ds = p * (dp - delta) * sm_scale                # delta [bq, 1]
    return p, ds


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, causal, sm_scale, block_q, block_k, kv_steps,
                   window=None):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    if window is not None:
        ki = _first_kv_block(qi, window, block_q, block_k) + ki

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
            q, k, v, do, lse_ref[0], delta_ref[0], qi, ki, causal, sm_scale,
            block_q, block_k, window,
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
                    *, causal, sm_scale, block_q, block_k, q_steps,
                    window=None, group=1, q_blocks=None):
    """One kv block of one KV head: the innermost grid axis walks the q
    blocks that see it, of every query head of the group in turn."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    step = pl.program_id(2)
    qi = step if group == 1 else step % q_steps
    if window is not None:
        qi = _first_q_block(ki, block_q, block_k) + qi

    @pl.when(step == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True
    if window is not None:  # not past the band's end, nor past the last block
        run = run & (qi * block_q <= ki * block_k + block_k + window - 2) & (qi < q_blocks)

    @pl.when(run)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        p, ds = _recompute_p_ds(
            q, k, v, do, lse_ref[0], delta_ref[0], qi, ki, causal, sm_scale,
            block_q, block_k, window,
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

    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    row_spec = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
    kv_steps, kv_index = _kv_index_maps(t, block_q, block_k, window, group)
    kv_spec_dq = pl.BlockSpec((1, block_k, d), kv_index)

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, kv_steps=kv_steps, window=window,
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
        name=_kernel_name("flash_bwd_dq", window),
    )(q, k, v, do, lse, delta)

    # dk/dv: grid iterates q blocks innermost for a fixed kv block (of every
    # query head of the group: the sum over the group is this kernel's).
    q_blocks = t // block_q
    if window is None:
        q_steps = q_blocks
        if group == 1:
            def q_index(b, i, j):
                return (b, j, 0)
        else:
            def q_index(b, i, j):
                return (b * group + j // q_steps, j % q_steps, 0)
    else:
        q_steps = _band_q_steps(t, window, block_q, block_k)

        def q_index(b, i, j):
            qi = jnp.minimum(_first_q_block(i, block_q, block_k) + j % q_steps, q_blocks - 1)
            return (b * group + j // q_steps, qi, 0)

    q_spec2 = pl.BlockSpec((1, block_q, d), q_index)
    kv_spec2 = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0))
    row_spec2 = pl.BlockSpec((1, block_q, 1), q_index)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, q_steps=q_steps,
            window=window, group=group, q_blocks=q_blocks,
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
        name=_kernel_name("flash_bwd_dkv", window),
    )(q, k, v, do, lse, delta)
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
    # windowed call keeps the forward's: they were cut to the band already.
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


WINDOW_BLOCK_CAP = 512  # windowed calls, all three kernels: tiles no wider than
                        # the band is deep, so most of a tile lies inside it


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
    query; blocks outside the band are not computed.

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
    q_cap, k_cap = (FWD_BLOCK_Q_CAP, FWD_BLOCK_K_CAP) if window is None else (
        max(128, min(WINDOW_BLOCK_CAP, window)),) * 2
    block_q = min(block_q, t) if block_q else (_auto_block(t, q_cap) or t + 1)
    block_k = min(block_k, t) if block_k else (_auto_block(t, k_cap) or t + 1)

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
