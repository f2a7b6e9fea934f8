"""Kimi delta attention (KDA, arXiv 2510.26692): the delta rule with a decay
per key channel, chunked for the TPU.

For one head, with keys and queries of K channels and values of V:

    S_t = (I - β_t k_t k_tᵀ) Diag(α_t) S_{t-1} + β_t k_t v_tᵀ,    o_t = scale · S_tᵀ q_t

``g = log α`` is handed in, each entry in ``(bound, 0)``; S starts at zero.

**Within a chunk of CHUNK positions** (the WY/UT form). With ``G_t`` the sum of
``g`` over the chunk up to and with ``t`` and ``S_0`` the state the chunk
starts from, ``u_t = β_t (v_t - (Diag(α_t) S_{t-1})ᵀ k_t)`` gives
``S_t = Diag(α_t) S_{t-1} + k_t u_tᵀ``, and over the chunk

    (I + A) U = Diag(β) (V - K̃ S_0),    A[t, s] = β_t Σ_c k_t[c] k_s[c] e^{G_t[c] - G_s[c]}  (s < t)
    O = Q̃ S_0 + M U,                    M[t, s] = scale Σ_c q_t[c] k_s[c] e^{G_t[c] - G_s[c]}  (s <= t)
    S_C = Diag(e^{G_C}) S_0 + K_Cᵀ U

with ``Q̃ = scale q e^{G}``, ``K̃ = k e^{G}`` and ``K_C = k e^{G_C - G}``. So
``U = U0 - W S_0`` with ``[W | U0] = (I + A)^{-1} Diag(β) [K̃ | V]``: all of it
is computed for every chunk independently, in parallel, and only ``U0 - W S``,
``Q̃ S + M U`` and the state's update walk the chunks in turn (the kernels).

**Where a decay is exponentiated.** ``e^{G}``, ``e^{G_C - G}`` and the state's
decay have exponents <= 0: they can underflow, as the true value does, and
never overflow. ``A`` and ``M`` pair a row ``t`` with a column ``s`` through
``e^{G_t - G_s}``, which is split at the first position ``r`` of ``t``'s
sub-chunk of SUB positions: ``e^{G_t - G_r}`` (``t`` at most SUB - 1 after
``r``: exponent in ``[bound (SUB - 1), 0]``) times ``e^{G_r - G_s}``, whose
exponent is <= 0 for a column before ``r`` and at most ``-bound (SUB - 1)``
(75 at a bound of -5: e^75 is a float32) for one inside the sub-chunk; a
column after the sub-chunk is hidden, and its exponent is clipped so that it
stays finite. A sum of ``g`` over a whole chunk is never exponentiated with a
positive sign: that is what the bounded ("safe") gate is for.

Products take their operands in the inputs' dtype (bfloat16 on the chip, at
the MXU's rate) and accumulate in float32; the state, the decays, ``U0`` and
the triangular solve (a product with (I + A)^{-1}) are float32.

**The backward.** The walk back gives the cotangents of ``Q̃, K_C, W, U0, M``
and the state's decay; they are pulled back to q, k, v, g and β chunk by
chunk, by hand. The solve's: with ``dR = (I + A)^{-ᵀ} [dW | dU0]``, the
right-hand side ``Diag(β) [K̃ | V]`` takes ``dR`` and ``A`` takes
``-dR [W | U0]ᵀ`` below its diagonal (the inverse is read, no second solve).
``A`` and ``M`` go back to k, q, β and the decays through the same split of
``e^{G_t - G_s}`` as the forward, a hidden column's clipped exponent passing
no gradient; ``Q̃``'s, ``K_C``'s, ``K̃``'s and the state's decay add their
terms to ``dG`` (``G_C``'s summed over the chunk), and ``dg`` is ``dG``
summed from the chunk's end back. Every term of ``dG`` is float32, and no
cotangent is rounded to the inputs' dtype before it is summed.

The kernels (Pallas, named for the device trace): ``kda_chunks`` computes the
parts, a block of chunks of one head a grid step, in VMEM (in the backward's
call it also writes the inverses); ``kda_fwd`` walks the chunks of one head
and writes ``o``; the backward walks the chunks again to write the state each
chunk starts from (``kda_bwd_states``), walks them back (``kda_bwd_grads``),
and ``kda_chunks_bwd`` pulls the parts' cotangents back, a block of chunks of
one head a grid step, as ``kda_chunks`` reads and writes them. ``_within_chunks``
is the parts in XLA, the reference both chunk kernels are tested against. Off
the TPU the same kernels run interpreted. Both directions take the heads
HEADS_A_PASS at a time, one pass after another.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..tracing import step_part

CHUNK = 64   # positions a chunk; the state is carried from one to the next
SUB = 16     # positions a decay is exponentiated across, at most
_CLIP = 80.0  # the largest exponent taken: e^80 is a float32, a hidden column's is clipped to it
# heads prepared and walked together, one such pass after another: at T 8192 a pass's
# [B, T, 8, 128] float32 parts are 32 MB each, where all 32 heads' are 128 (PERF.md, PR 37)
HEADS_A_PASS = 8


def _dot(a, b, contract=(1, 0)):
    """a · b contracting a's dimension ``contract[0]`` with b's ``contract[1]``,
    float32 accumulation; float32 operands at full precision."""
    prec = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jax.lax.dot_general(a, b, (((contract[0],), (contract[1],)), ((), ())),
                               preferred_element_type=jnp.float32, precision=prec)


# ---------------------------------------------------------------------------
# Within every chunk at once (XLA)
# ---------------------------------------------------------------------------

def _within_chunks(q, k, v, g, beta, scale):
    """q, k [N, C, K], v [N, C, V] in the products' dtype; g [N, C, K] and beta
    [N, C] float32; N counts (batch, head, chunk). Returns the chunks' parts in
    the order the walks take them: Q̃, K_C, W (products' dtype), U0 (float32),
    M (products' dtype) and the state's decay [N, 1, K] (float32)."""
    n, c, dk = q.shape
    dt = q.dtype
    cum = jnp.cumsum(g, axis=1)                                    # G, <= 0
    subs = c // SUB
    by_sub = cum.reshape(n, subs, SUB, dk)
    # G at each sub-chunk's first position: it cancels from every product, so no gradient goes through it
    first = jax.lax.stop_gradient(by_sub[:, :, :1, :])
    row = jnp.exp(by_sub - first).reshape(n, c, dk)                 # e^{G_t - G_r}, exponent in [bound (SUB-1), 0]
    col = jnp.exp(jnp.minimum(first - cum[:, None, :, :], _CLIP))   # e^{G_r - G_s} [N, subs, C, K]
    kf, qf = k.astype(jnp.float32), q.astype(jnp.float32) * scale
    k_col = (kf[:, None] * col).astype(dt)

    def pairs(rows):  # [N, C, K] rows of each sub-chunk against every column, relative to its r
        rows = (rows * row).astype(dt).reshape(n, subs, SUB, dk)
        return jnp.einsum("nbik,nbjk->nbij", rows, k_col,
                          preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST if dt == jnp.float32 else None
                          ).reshape(n, c, c)

    t = jnp.arange(c)
    a = jnp.where(t[:, None] > t[None, :], pairs(kf), 0.0) * beta[:, :, None]
    m = jnp.where(t[:, None] >= t[None, :], pairs(qf), 0.0)
    decay = jnp.exp(cum)                                            # e^{G_t}
    last = cum[:, -1:, :]                                           # G_C
    rhs = jnp.concatenate([kf * decay, v.astype(jnp.float32)], axis=-1) * beta[:, :, None]
    solved = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(c, dtype=jnp.float32), rhs, lower=True, unit_diagonal=True)
    w, u0 = solved[..., :dk], solved[..., dk:]
    return ((qf * decay).astype(dt), (kf * jnp.exp(last - cum)).astype(dt), w.astype(dt), u0,
            m.astype(dt), jnp.exp(last))


# ---------------------------------------------------------------------------
# Within a block of chunks (Pallas): the forward's parts, in VMEM
# ---------------------------------------------------------------------------
#
# ``_within_chunks`` on one chunk's values, step for step: the same roundings
# to the products' dtype, the same float32 decays, A and solve. What differs is
# the order of float32 sums: the cumulative sum of g is taken by doubling, and
# the solve is a product with the inverse of I + A.

def _cumsum_rows(x, reverse=False):
    """The cumulative sum of x [C, .] down its rows (up them, ``reverse``), by doubling."""
    from jax.experimental.pallas import tpu as pltpu

    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    n, shift = x.shape[0], 1
    while shift < n:
        if reverse:  # x[t + shift] rolled to t
            x = x + jnp.where(row < n - shift, pltpu.roll(x, n - shift, 0), 0.0)
        else:
            x = x + jnp.where(row >= shift, pltpu.roll(x, shift, 0), 0.0)
        shift *= 2
    return x


PACK = 128 // CHUNK  # chunks whose A and inverse sit side by side in the 128 lanes of a row


def _side_by_side(x, y):
    """x_j @ y_j for the chunks side by side in x, y [C, PACK * C]: one
    product with the y_j on the diagonal of a [PACK * C, PACK * C] operand."""
    c, width = x.shape
    r = jax.lax.broadcasted_iota(jnp.int32, (width, width), 0)
    l = jax.lax.broadcasted_iota(jnp.int32, (width, width), 1)
    return _dot(x, jnp.where(r // c == l // c, jnp.concatenate([y] * (width // c), axis=0), 0.0))


def _unit_lower_inverses(a):
    """(I + a_j)^{-1} for the chunks side by side in ``a`` [C, PACK * C]
    float32, each a_j zero on and above its diagonal. The diagonal blocks of
    SUB are inverted as (I - N)(I + N²)(I + N⁴)(I + N⁸) (N is nilpotent of
    index SUB); with ``F`` = their inverse times what lies below them,
    nilpotent of index C / SUB, (I + a_j)^{-1} = (I + F)^{-1} times that
    inverse, and (I + F)^{-1} is the same product over F's powers."""
    c, width = a.shape
    t = jax.lax.broadcasted_iota(jnp.int32, (c, width), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (c, width), 1) % c
    eye = jnp.where(t == s, 1.0, 0.0)

    def neumann(n, index):  # (I + n)^{-1} for n nilpotent of index ``index``, a power of 2
        inv, power = eye - n, n
        for _ in range(int(math.log2(index)) - 1):
            power = _side_by_side(power, power)
            inv = inv + _side_by_side(inv, power)
        return inv

    within = t // SUB == s // SUB
    inv = neumann(jnp.where(within, a, 0.0), SUB)
    return _side_by_side(neumann(_side_by_side(inv, jnp.where(within, 0.0, a)), c // SUB), inv)


def _head_beta(beta_ref, rows, head):
    """Head ``head``'s column of ``beta_ref`` [1, chunks * C, H] at ``rows``: [C, 1]."""
    by_head = beta_ref[0, rows, :]
    pick = jax.lax.broadcasted_iota(jnp.int32, by_head.shape, 1) == head
    return jnp.sum(jnp.where(pick, by_head, 0.0), axis=1, keepdims=True)


def _chunks_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref,
                   qt_ref, kc_ref, w_ref, u0_ref, m_ref, decay_ref, *inv_ref, scale):
    """A block of chunks of one head: ``*_ref`` [1, chunks * C, .] in (the
    head's column of ``beta_ref`` [1, chunks * C, H] picked by the grid's
    head); the parts out, [1, chunks, C, .], and where ``inv_ref`` is given
    the inverses (I + A)^{-1}, [1, chunks / PACK, C, PACK * C], PACK chunks'
    side by side. PACK chunks a step, UNROLL such steps a loop step."""
    from jax.experimental import pallas as pl

    c = CHUNK
    dt = q_ref.dtype
    head = pl.program_id(1)
    t = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    tp = jax.lax.broadcasted_iota(jnp.int32, (c, PACK * c), 0)
    sp = jax.lax.broadcasted_iota(jnp.int32, (c, PACK * c), 1)

    def chunk(n):
        """Chunk n's parts but W and U0, and what its solve takes."""
        rows = pl.ds(pl.multiple_of(n * c, c), c)
        kf = k_ref[0, rows, :].astype(jnp.float32)
        qf = q_ref[0, rows, :].astype(jnp.float32) * scale
        vf = v_ref[0, rows, :].astype(jnp.float32)
        beta = _head_beta(beta_ref, rows, head)                                  # [C, 1]
        cum = _cumsum_rows(g_ref[0, rows, :])                                    # G, <= 0
        k_rows, k_cols, m_rows = [], [], []
        for sub in range(c // SUB):
            here = slice(sub * SUB, (sub + 1) * SUB)
            first = cum[sub * SUB:sub * SUB + 1]                                 # G_r
            row = jnp.exp(cum[here] - first)                                     # e^{G_t - G_r}
            k_col = (kf * jnp.exp(jnp.minimum(first - cum, _CLIP))).astype(dt)   # k e^{G_r - G_s}
            k_rows.append((kf[here] * row).astype(dt))
            k_cols.append(k_col)
            m_rows.append(_dot((qf[here] * row).astype(dt), k_col, (1, 1)))
        decay = jnp.exp(cum)                                                     # e^{G_t}
        last = cum[c - 1:c]                                                      # G_C
        qt_ref[0, n] = (qf * decay).astype(dt)
        kc_ref[0, n] = (kf * jnp.exp(last - cum)).astype(dt)
        m_ref[0, n] = jnp.where(t >= s, jnp.concatenate(m_rows, axis=0), 0.0).astype(dt)
        decay_ref[0, n] = jnp.exp(last)
        return k_rows, k_cols, beta, kf * decay * beta, vf * beta

    def pack(p):
        """Chunks p * PACK .. p * PACK + PACK - 1: their A side by side, one inverse."""
        first = p * PACK
        got = [chunk(first + j) for j in range(PACK)]

        def placed(x, j):  # x in the j-th of PACK places, zeros in the others
            return jnp.concatenate([x if i == j else jnp.zeros_like(x) for i in range(PACK)], axis=0)

        # A's rows of each sub-chunk for all PACK chunks at once: the chunks' rows side by
        # side against their columns on the diagonal
        a_rows = [_dot(jnp.concatenate([k_rows[sub] for k_rows, *_ in got], axis=1),
                       jnp.concatenate([placed(k_cols[sub], j)
                                        for j, (_, k_cols, *_) in enumerate(got)], axis=1), (1, 1))
                  for sub in range(c // SUB)]
        beta = sum(jnp.where(sp // c == j, b, 0.0) for j, (_, _, b, _, _) in enumerate(got))
        inv = _unit_lower_inverses(jnp.where(tp > sp % c, jnp.concatenate(a_rows, axis=0), 0.0) * beta)
        for j, (*_, rk, rv) in enumerate(got):
            w_ref[0, first + j] = _dot(inv, placed(rk, j)).astype(dt)
            u0_ref[0, first + j] = _dot(inv, placed(rv, j))
        if inv_ref:
            inv_ref[0][0, p] = inv

    _in_packs(pack, qt_ref.shape[1] // PACK)


def _in_packs(pack, steps):
    """``pack(p)`` for p in 0 .. steps - 1, UNROLL of them a loop step."""
    def body(i, carry):
        for j in range(UNROLL):
            pack(i * UNROLL + j)
        return carry

    jax.lax.fori_loop(0, steps // UNROLL, body, 0)
    for p in range(steps - steps % UNROLL, steps):
        pack(p)


UNROLL = 2  # PACK chunks at a time, UNROLL times a loop step
CHUNKS_A_STEP = 16  # chunks of one head a grid step of ``kda_chunks``, at most


class _Blocks:
    """The block specs ``kda_chunks`` and ``kda_chunks_bwd`` share: a block of
    ``per`` chunks of one head a grid step (b, h, n)."""

    def __init__(self, b, t, h):
        from jax.experimental import pallas as pl

        self.pl, self.b, self.t, self.h = pl, b, t, h
        self.nc = nc = t // CHUNK
        self.per = max(n for n in range(PACK, min(nc, CHUNKS_A_STEP) + 1, PACK) if nc % n == 0)
        self.grid = (b, h, nc // self.per)

    def cols(self, width):  # a head's rows of [B * H, T, width]
        return self.pl.BlockSpec((1, self.per * CHUNK, width), lambda i, j, n: (i * self.h + j, n, 0))

    def beta(self):  # the rows of beta [B, T, H], every head
        return self.pl.BlockSpec((1, self.per * CHUNK, self.h), lambda i, j, n: (i, n, 0))

    def parts(self, shape, per=None):  # a head's block of [B * H, ., *shape]: its chunks, or ``per`` items
        return self.pl.BlockSpec((1, per or self.per) + shape,
                                 lambda i, j, n: (i * self.h + j, n) + (0,) * len(shape))

    def row(self):  # a head's positions of [B * H, 1, T]
        return self.pl.BlockSpec((1, 1, self.per * CHUNK), lambda i, j, n: (i * self.h + j, 0, n))

    def head_major(self, x):
        """[B, T, H, .] -> [B * H, T, .]"""
        return jnp.moveaxis(x, 2, 1).reshape(self.b * self.h, self.t, -1)

    def call(self, kernel, in_specs, out_specs, out_shape, interpret, name):
        from jax.experimental.pallas import tpu as pltpu

        return self.pl.pallas_call(
            kernel, grid=self.grid, in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel")),
            interpret=interpret, name=name)


def _chunk_parts(q, k, v, g, beta, scale, interpret, inverse=False):
    """What ``_parts`` gives, from the Pallas kernel ``kda_chunks``: q, k, v, g
    [B, T, H, .] read head-major, as ``[B*H, T, .]`` (the layout a pass holds
    them in, so no copy), and beta [B, T, H]; a block of chunks of one head a
    grid step; the parts written as the walks read them, [B*H, NC, C, .].
    With ``inverse`` (the backward's call) also the chunks' (I + A)^{-1},
    float32 [B*H, NC / PACK, C, PACK * C], PACK chunks' side by side."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    blocks, c = _Blocks(b, t, h), CHUNK
    shapes = [(c, dk), (c, dk), (c, dk), (c, dv), (c, c), (1, dk)]
    dtypes = [q.dtype] * 3 + [jnp.float32, q.dtype, jnp.float32]
    out_specs = [blocks.parts(shape) for shape in shapes]
    out_shape = [jax.ShapeDtypeStruct((b * h, blocks.nc) + shape, dtype)
                 for shape, dtype in zip(shapes, dtypes)]
    if inverse:
        out_specs.append(blocks.parts((c, PACK * c), blocks.per // PACK))
        out_shape.append(jax.ShapeDtypeStruct((b * h, blocks.nc // PACK, c, PACK * c), jnp.float32))
    return blocks.call(
        functools.partial(_chunks_kernel, scale=scale),
        [blocks.cols(dk), blocks.cols(dk), blocks.cols(dv), blocks.cols(dk), blocks.beta()],
        out_specs, out_shape, interpret, "kda_chunks",
    )(*(blocks.head_major(x) for x in (q, k, v, g)), beta)


# ---------------------------------------------------------------------------
# The parts pulled back, a block of chunks (Pallas), in VMEM
# ---------------------------------------------------------------------------
#
# ``_within_chunks``' pullback, written out: given the cotangents of Q̃, K_C,
# W, U0, M and the decay (the walk's), those of q, k, v, g and β. Products take
# their operands in the inputs' dtype and accumulate in float32, as the
# forward's; the products with (I + A)^{-1}, the decays and every term of the
# gate's gradient are float32, and no cotangent is rounded to the inputs' dtype
# before it is summed.

def _chunks_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, inv_ref, w_ref, u0_ref,
                       dqt_ref, dkc_ref, dw_ref, du0_ref, dm_ref, ddecay_ref,
                       dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, *, scale):
    """A block of chunks of one head, laid out as ``_chunks_kernel`` reads and
    writes them; dq, dk, dv, dg out as q is read, dβ [1, 1, chunks * C]."""
    from jax.experimental import pallas as pl

    c = CHUNK
    dt = q_ref.dtype
    head = pl.program_id(1)
    t = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    tp = jax.lax.broadcasted_iota(jnp.int32, (c, PACK * c), 0)
    sp = jax.lax.broadcasted_iota(jnp.int32, (c, PACK * c), 1)

    def chunk(n, dr_k, dr_v):
        """Chunk n's cotangents, given dR = (I + A)^{-T} [dW | dU0] = [dr_k | dr_v],
        that of the solve's right-hand side Diag(β) [K̃ | V]; β's as a column."""
        rows = pl.ds(pl.multiple_of(n * c, c), c)
        kf = k_ref[0, rows, :].astype(jnp.float32)
        qf = q_ref[0, rows, :].astype(jnp.float32) * scale
        vf = v_ref[0, rows, :].astype(jnp.float32)
        beta = _head_beta(beta_ref, rows, head)                                  # [C, 1]
        cum = _cumsum_rows(g_ref[0, rows, :])                                    # G
        decay = jnp.exp(cum)                                                     # e^{G_t}
        last = cum[c - 1:c]                                                      # G_C
        # the solve: dA = -dR [W | U0]ᵀ below the diagonal
        da = -(_dot(dr_k.astype(dt), w_ref[0, n], (1, 1))
               + _dot(dr_v.astype(dt), u0_ref[0, n].astype(dt), (1, 1)))
        da = jnp.where(t > s, da, 0.0)
        # its right-hand side, Diag(β) [k e^{G} | v]
        kt = kf * decay
        dk = dr_k * decay * beta
        dg = dr_k * kt * beta
        dv = dr_v * beta
        dbeta = jnp.sum(dr_k * kt, axis=1, keepdims=True) + jnp.sum(dr_v * vf, axis=1, keepdims=True)
        # Q̃ = scale q e^{G}
        dqt = dqt_ref[0, n].astype(jnp.float32)
        dq = dqt * decay
        dg = dg + dqt * qf * decay
        # K_C = k e^{G_C - G}, and the state's decay e^{G_C}
        to_last = jnp.exp(last - cum)
        dkc = dkc_ref[0, n].astype(jnp.float32)
        dk = dk + dkc * to_last
        kc_term = dkc * kf * to_last
        dg = dg - kc_term
        at_last = jnp.sum(kc_term, axis=0, keepdims=True) + ddecay_ref[0, n] * jnp.exp(last)
        # A and M, a sub-chunk's rows at a time against every column, split at its first row r
        da_raw = da * beta                                                       # A = β A_raw
        dm = jnp.where(t >= s, dm_ref[0, n].astype(jnp.float32), 0.0)
        by_rows = []
        for sub in range(c // SUB):
            here = slice(sub * SUB, (sub + 1) * SUB)
            first = cum[sub * SUB:sub * SUB + 1]                                 # G_r
            row = jnp.exp(cum[here] - first)                                     # e^{G_t - G_r}
            exponent = first - cum
            col = jnp.exp(jnp.minimum(exponent, _CLIP))                          # e^{G_r - G_s}
            k_col = kf * col
            k_row, q_row = kf[here] * row, qf[here] * row
            rows_b = jnp.concatenate([k_row, q_row], axis=0).astype(dt)          # [2 SUB, K]
            k_col_b = k_col.astype(dt)
            a_raw = _dot(rows_b[:SUB], k_col_b, (1, 1))                          # [SUB, C]
            lhs = jnp.concatenate([da_raw[here], dm[here]], axis=0).astype(dt)   # [2 SUB, C]
            d_rows = _dot(lhs, k_col_b)                                          # [2 SUB, K]
            d_col = _dot(lhs, rows_b, (0, 0))                                    # [C, K]
            dk = dk + d_col * col
            # a hidden column's exponent is clipped: no gradient goes through it
            dg = dg - jnp.where(exponent < _CLIP, d_col * k_col, 0.0)
            by_rows.append((d_rows[:SUB] * row, d_rows[SUB:] * row,
                            d_rows[:SUB] * k_row + d_rows[SUB:] * q_row,
                            jnp.sum(da[here] * a_raw, axis=1, keepdims=True)))
        dk_rows, dq_rows, dg_rows, dbeta_rows = (jnp.concatenate(x, axis=0) for x in zip(*by_rows))
        t_k = jax.lax.broadcasted_iota(jnp.int32, dg.shape, 0)
        dg = dg + dg_rows + jnp.where(t_k == c - 1, at_last, 0.0)
        dq_ref[0, rows, :] = ((dq + dq_rows) * scale).astype(dq_ref.dtype)
        dk_ref[0, rows, :] = (dk + dk_rows).astype(dk_ref.dtype)
        dv_ref[0, rows, :] = dv.astype(dv_ref.dtype)
        dg_ref[0, rows, :] = _cumsum_rows(dg, reverse=True)                      # G = cumsum(g)
        return dbeta + dbeta_rows

    def pack(p):
        """Chunks p * PACK .. p * PACK + PACK - 1: dR for all of them in one
        product with their inverses on the diagonal; dβ as one row."""
        first = p * PACK
        inv = inv_ref[0, p]                                                      # [C, PACK * C]
        diagonal = jnp.concatenate([jnp.where(sp // c == j, inv, 0.0) for j in range(PACK)], axis=0)
        dr_k, dr_v = (_dot(diagonal, jnp.concatenate([ref[0, first + j].astype(jnp.float32)
                                                      for j in range(PACK)], axis=0), (0, 0))
                      for ref in (dw_ref, du0_ref))
        dbetas = [chunk(first + j, dr_k[j * c:(j + 1) * c], dr_v[j * c:(j + 1) * c])
                  for j in range(PACK)]
        # chunk j's column along its lanes, then each lane's own row: a row [1, PACK * C]
        spread = sum(jnp.where(sp // c == j, d, 0.0) for j, d in enumerate(dbetas))
        dbeta_ref[0, :, pl.ds(pl.multiple_of(first * c, PACK * c), PACK * c)] = jnp.sum(
            jnp.where(tp == sp % c, spread, 0.0), axis=0, keepdims=True)

    _in_packs(pack, dqt_ref.shape[1] // PACK)


def _chunk_grads(q, k, v, g, beta, inverse, w, u0, d_parts, scale, interpret):
    """The cotangents of q, k, v, g [B, T, H, .] and beta [B, T, H] from those
    of the parts (``d_parts``, as ``kda_bwd_grads`` writes them), through the
    Pallas kernel ``kda_chunks_bwd``: a block of chunks of one head a grid
    step, read as ``kda_chunks`` reads and writes them; ``inverse``, ``w``,
    ``u0``: what ``kda_chunks`` wrote in the backward's call."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    blocks, c = _Blocks(b, t, h), CHUNK
    shapes = [(c, dk), (c, dk), (c, dk), (c, dv), (c, c), (1, dk)]
    dq, dk_, dv_, dg, dbeta = blocks.call(
        functools.partial(_chunks_bwd_kernel, scale=scale),
        [blocks.cols(dk), blocks.cols(dk), blocks.cols(dv), blocks.cols(dk), blocks.beta(),
         blocks.parts((c, PACK * c), blocks.per // PACK), blocks.parts((c, dk)), blocks.parts((c, dv))]
        + [blocks.parts(shape) for shape in shapes],
        [blocks.cols(dk), blocks.cols(dk), blocks.cols(dv), blocks.cols(dk), blocks.row()],
        [jax.ShapeDtypeStruct((b * h, t, width), dtype) for width, dtype in
         ((dk, q.dtype), (dk, k.dtype), (dv, v.dtype), (dk, jnp.float32))]
        + [jax.ShapeDtypeStruct((b * h, 1, t), jnp.float32)],
        interpret, "kda_chunks_bwd",
    )(*(blocks.head_major(x) for x in (q, k, v, g)), beta, inverse, w, u0, *d_parts)
    return tuple(jnp.moveaxis(x.reshape(b, h, t, -1), 1, 2) for x in (dq, dk_, dv_, dg)) + (
        jnp.moveaxis(dbeta.reshape(b, h, t), 1, 2),)


# ---------------------------------------------------------------------------
# One chunk's step of the walks, on values (the kernels' bodies)
# ---------------------------------------------------------------------------
#
# The walks hold the state transposed, ``R = Sᵀ`` [V, K]: the decay is then a
# row [1, K] that scales R's columns, along the lanes, and neither it nor its
# cotangent is ever a [K, 1] column (128 times its size in a TPU's tiles).

def _chunk_forward(r, qt, kc, w, u0, m, decay):
    """(o, the next state) from the state ``r`` = Sᵀ [V, K] float32 a chunk starts from."""
    rb = r.astype(qt.dtype)
    u = u0 - _dot(w, rb, (1, 1))
    ub = u.astype(qt.dtype)
    o = _dot(qt, rb, (1, 1)) + _dot(m, ub)
    return o, decay * r + _dot(ub, kc, (0, 0))


def _chunk_backward(r, dr, qt, kc, w, u0, m, decay, do):
    """The cotangents of one chunk's parts, and of the state it starts from,
    given ``dr``, that of the state it hands on, and ``do``, that of its output."""
    dt = qt.dtype
    rb = r.astype(dt)
    ub = (u0 - _dot(w, rb, (1, 1))).astype(dt)
    drb = dr.astype(dt)
    du = _dot(m, do, (0, 0)) + _dot(kc, drb, (1, 1))
    dub = du.astype(dt)
    parts = (
        _dot(do, rb),                               # Q̃
        _dot(ub, drb),                              # K_C
        -_dot(dub, rb),                             # W
        du,                                         # U0
        _dot(do, ub, (1, 1)),                       # M
        jnp.sum(r * dr, axis=0, keepdims=True),     # the decay
    )
    dr_before = decay * dr + _dot(do, qt, (0, 0)) - _dot(dub, w, (0, 0))
    return parts, dr_before


# ---------------------------------------------------------------------------
# The walks: Pallas, interpreted off the TPU
# ---------------------------------------------------------------------------

def _specs(shapes, chunks=None):
    """A block of one chunk of one (batch, head) a grid step; ``chunks``: walked
    from the last chunk back."""
    from jax.experimental import pallas as pl

    def index(b, n):
        return (b, n if chunks is None else chunks - 1 - n, 0, 0)

    return [pl.BlockSpec((1, 1) + tuple(shape), index) for shape in shapes]


def _walk_kernel(qt_ref, kc_ref, w_ref, u0_ref, m_ref, decay_ref, out_ref, s_ref, *, states):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _start():
        s_ref[:] = jnp.zeros_like(s_ref)

    s = s_ref[:]
    o, s_ref[:] = _chunk_forward(s, qt_ref[0, 0], kc_ref[0, 0], w_ref[0, 0], u0_ref[0, 0],
                                 m_ref[0, 0], decay_ref[0, 0])
    out_ref[0, 0] = s if states else o.astype(out_ref.dtype)


def _walk(parts, out_dtype, interpret, states):
    """Chunks of [B*H, NC, ...] in turn: ``o`` [B*H, NC, C, V], or with
    ``states`` the state each chunk starts from, Sᵀ [B*H, NC, V, K] float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    qt, kc, w, u0, m, decay = parts
    bh, nc, c, dk = qt.shape
    dv = u0.shape[-1]
    out = (dv, dk) if states else (c, dv)
    return pl.pallas_call(
        functools.partial(_walk_kernel, states=states),
        grid=(bh, nc),
        in_specs=_specs([(c, dk), (c, dk), (c, dk), (c, dv), (c, c), (1, dk)]),
        out_specs=_specs([out])[0],
        out_shape=jax.ShapeDtypeStruct((bh, nc) + out, jnp.float32 if states else out_dtype),
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="kda_bwd_states" if states else "kda_fwd",
    )(*parts)


def _grads_kernel(qt_ref, kc_ref, w_ref, u0_ref, m_ref, decay_ref, s_ref, do_ref,
                  dqt_ref, dkc_ref, dw_ref, du0_ref, dm_ref, ddecay_ref, ds_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _start():
        ds_ref[:] = jnp.zeros_like(ds_ref)

    parts, ds_ref[:] = _chunk_backward(
        s_ref[0, 0], ds_ref[:], qt_ref[0, 0], kc_ref[0, 0], w_ref[0, 0], u0_ref[0, 0],
        m_ref[0, 0], decay_ref[0, 0], do_ref[0, 0])
    for ref, value in zip((dqt_ref, dkc_ref, dw_ref, du0_ref, dm_ref, ddecay_ref), parts):
        ref[0, 0] = value.astype(ref.dtype)


def _walk_back(parts, states, do, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, nc, c, dk = parts[0].shape
    dv = do.shape[-1]
    shapes = [(c, dk), (c, dk), (c, dk), (c, dv), (c, c), (1, dk)]
    return tuple(pl.pallas_call(
        _grads_kernel,
        grid=(bh, nc),
        in_specs=_specs(shapes + [(dv, dk), (c, dv)], nc),
        out_specs=_specs(shapes, nc),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in parts],
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="kda_bwd_grads",
    )(*parts, states, do))


# ---------------------------------------------------------------------------
# The op
# ---------------------------------------------------------------------------

def _to_chunks(x):
    """[B, T, H, ...] -> [B*H*NC, C, ...]."""
    b, t, h = x.shape[:3]
    x = jnp.moveaxis(x, 2, 1)
    return x.reshape((b * h * (t // CHUNK), CHUNK) + x.shape[3:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kda(q, k, v, g, beta, scale, interpret):
    return _kda_fwd(q, k, v, g, beta, scale, interpret)[0]


def _parts(q, k, v, g, beta, scale):
    b, t, h = q.shape[:3]
    flat = _within_chunks(*(_to_chunks(x) for x in (q, k, v, g, beta)), scale)
    return tuple(p.reshape((b * h, t // CHUNK) + p.shape[1:]) for p in flat)


def _by_heads(x, passes):
    """[B, T, H, ...] -> [passes, B, T, H / passes, ...]."""
    b, t, h = x.shape[:3]
    return jnp.moveaxis(x.reshape((b, t, passes, h // passes) + x.shape[3:]), 2, 0)


def _from_heads(x):
    """[passes, B, T, H / passes, ...] -> [B, T, H, ...]."""
    passes, b, t, h = x.shape[:4]
    return jnp.moveaxis(x, 0, 2).reshape((b, t, passes * h) + x.shape[4:])


def _in_passes(fn, *xs):
    """``fn`` over HEADS_A_PASS heads of ``xs`` [B, T, H, ...] at a time, one
    pass after another, so that only one pass's chunk parts are alive."""
    passes = xs[0].shape[2] // math.gcd(xs[0].shape[2], HEADS_A_PASS)
    out = jax.lax.map(lambda x: fn(*x), tuple(_by_heads(x, passes) for x in xs))
    return jax.tree.map(_from_heads, out)


def _forward(q, k, v, g, beta, scale, interpret):
    b, t, h, _ = q.shape
    with step_part("kda_parts"):
        parts = _chunk_parts(q, k, v, g, beta, scale, interpret)
    with step_part("kda_walks"):
        o = _walk(parts, q.dtype, interpret, states=False)
    return jnp.moveaxis(o.reshape(b, h, t, -1), 1, 2)


def _backward(q, k, v, g, beta, do, scale, interpret):
    b, t, h, _ = do.shape
    with step_part("kda_parts"):
        *parts, inverse = _chunk_parts(q, k, v, g, beta, scale, interpret, inverse=True)
    do = jnp.moveaxis(do, 2, 1).reshape(b * h, t // CHUNK, CHUNK, -1)
    with step_part("kda_walks"):
        states = _walk(parts, do.dtype, interpret, states=True)
        d_parts = _walk_back(parts, states, do, interpret)
    with step_part("kda_parts"):
        return _chunk_grads(q, k, v, g, beta, inverse, parts[2], parts[3], d_parts, scale, interpret)


def _kda_fwd(q, k, v, g, beta, scale, interpret):
    o = _in_passes(functools.partial(_forward, scale=scale, interpret=interpret), q, k, v, g, beta)
    return o, (q, k, v, g, beta)


def _kda_bwd(scale, interpret, res, do):
    return _in_passes(functools.partial(_backward, scale=scale, interpret=interpret), *res, do)


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda(q, k, v, g, beta, scale: Optional[float] = None):
    """Kimi delta attention over [B, T, H, K] ``q``, ``k`` (L2-normalised per
    head, as the layer hands them), ``v`` [B, T, H, V], ``g`` = log α [B, T, H, K]
    (float32, each entry in ``(bound, 0)`` with ``bound * (SUB - 1)`` above
    -_CLIP) and ``beta`` [B, T, H] (float32); ``o`` [B, T, H, V] in ``q``'s dtype.
    ``scale`` defaults to K^-1/2. A sequence that is no whole number of PACK
    chunks is padded at its end (a later position changes no earlier output). The
    kernels compile on a TPU and run interpreted elsewhere."""
    from .flash_attention import _on_tpu

    t = q.shape[1]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    pad = -t % (CHUNK * PACK)
    if pad:
        q, k, v, g = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) for x in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    o = _kda(q, k, v, g.astype(jnp.float32), beta.astype(jnp.float32), float(scale), not _on_tpu())
    return o[:, :t]


def kda_recurrence(q, k, v, g, beta, scale: Optional[float] = None):
    """The recurrence itself, a position at a time, float32: what ``kda`` computes."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    b, _, h, dk = q.shape
    dv = v.shape[-1]

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x                                  # [B, H, .]
        s = jnp.exp(g_t)[..., None] * s
        kv = jnp.einsum("bhk,bhkv->bhv", k_t, s, precision="highest")
        s = s + b_t[..., None, None] * k_t[..., None] * (v_t - kv)[..., None, :]
        return s, scale * jnp.einsum("bhk,bhkv->bhv", q_t, s, precision="highest")

    xs = tuple(jnp.swapaxes(x.astype(jnp.float32), 0, 1) for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), jnp.float32), xs)
    return jnp.swapaxes(o, 0, 1)
