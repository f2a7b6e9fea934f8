"""Grouped matrix products for routed experts — Pallas TPU kernels over a
tile-aligned layout.

The rows of ``lhs`` [M, K] are tokens sorted by expert; group ``g``'s rows are
multiplied by ``rhs[g]`` [K, N]. The layout is *tile-aligned*: every group
starts on a multiple of ``tile`` rows and owns at least one tile, the rows that
pad a group out are zero, so a tile belongs to exactly one group and no kernel
masks anything. ``tile_group[i]`` names tile ``i``'s group and ``num_tiles``
how many tiles are in use; the buffer ends in ``CHUNK_TILES - 1`` tiles that
never are.

A grid step is a *chunk*: up to ``CHUNK_TILES`` consecutive tiles of one group
(``chunk_plan``; its arrays are scalar-prefetched, so the rows and the block of
``rhs`` a step reads are chosen by data). A step always reads ``CHUNK_TILES``
tiles of rows — at the load a layer expects a group is one chunk, and the step
then lasts as long as fetching the group's float32 block does, which is what
bounds these kernels (PERF.md, PR 30) — and writes or sums only the group's
own. Chunks past the ones in use are skipped: their block indices are clamped
to the last in use (nothing is fetched), and rows of tiles not in use are never
written — the caller reads only rows it placed.

Three kernels, named for the trace: ``expert_gmm_fwd`` (lhs · rhs[g]),
``expert_gmm_dlhs`` (the same product against rhs[g]ᵀ: the input's gradient)
and ``expert_gmm_dw`` (lhsᵀ · dout summed over a group's tiles: the weights'
gradient, float32). ``rhs`` is read in the dtype it is stored in (float32
parameters) and rounded to the rows' dtype in VMEM, block by block: a
separate cast would pass over the experts' weights twice more.

Off the chip ``jax.lax.ragged_dot`` computes the same products (the groups'
aligned sizes are handed to it); ``interpret=True`` forces the kernels in
Pallas interpret mode, for the tests.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import _dot_precision, _use_kernel

TILE = 128                      # rows of a tile: the MXU's edge on a v5e
CHUNK_TILES = 3                 # tiles a grid step multiplies at once; the buffer ends in CHUNK_TILES - 1 spare tiles
_VMEM_LIMIT = 64 * 1024 * 1024  # two float32 [2048, 512] blocks in flight, the rows and a chunk's result


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT)


def chunk_plan(tile_group, num_tiles, groups: int):
    """The grid the kernels walk: a group's tiles in runs of up to CHUNK_TILES.
    Returns ``group`` [C], ``tile`` [C] (a chunk's first tile), ``valid`` [C]
    (how many of its tiles are the group's) and ``num`` [1], the chunks in use;
    C = ceil(tiles / CHUNK_TILES) + groups is enough for any routing."""
    tiles = tile_group.shape[0]
    in_use = jnp.arange(tiles, dtype=jnp.int32) < num_tiles[0]
    tiles_of = jnp.sum(
        (tile_group[:, None] == jnp.arange(groups, dtype=jnp.int32)[None, :]) & in_use[:, None],
        axis=0, dtype=jnp.int32)
    first_tile = jnp.cumsum(tiles_of) - tiles_of
    chunks_of = (tiles_of + CHUNK_TILES - 1) // CHUNK_TILES
    first_chunk = jnp.cumsum(chunks_of) - chunks_of
    chunk = jnp.arange(-(-tiles // CHUNK_TILES) + groups, dtype=jnp.int32)
    group = jnp.clip(jnp.sum(chunk[:, None] >= first_chunk[None, :], axis=1) - 1, 0, groups - 1)
    within = (chunk - first_chunk[group]) * CHUNK_TILES
    valid = jnp.clip(tiles_of[group] - within, 0, CHUNK_TILES)
    as_int = lambda x: x.astype(jnp.int32)
    return as_int(group), as_int(first_tile[group] + within), as_int(valid), as_int(
        jnp.sum(chunks_of).reshape(1))


def _chunk_rows(c, group, tile, valid, num):
    """Chunk ``c``'s CHUNK_TILES tiles of rows, by their first row; a chunk
    past the ones in use maps to the last in use (nothing is fetched)."""
    return (tile[jnp.minimum(c, num[0] - 1)] * TILE, 0)


def _chunk_group(c, group, tile, valid, num):
    """The [K, N] block of chunk ``c``'s group (clamped as the rows are)."""
    return (group[jnp.minimum(c, num[0] - 1)], 0, 0)


def _gmm_kernel(group, tile, valid, num, lhs_ref, rhs_ref, out_ref, result, sem, *,
                transpose_rhs: bool):
    """One chunk: CHUNK_TILES tiles of rows against their group's block, as one
    product. The rows past the group's own are computed and not written: the
    result leaves VMEM a tile at a time, the valid ones only, while the next
    chunks are multiplied (two slots)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c = pl.program_id(0)

    def writes(step, act):  # start or wait for the copies of ``step``'s valid tiles
        slot = step % 2
        for t in range(CHUNK_TILES):
            copy = pltpu.make_async_copy(
                result.at[slot, pl.ds(t * TILE, TILE)],
                out_ref.at[pl.ds(pl.multiple_of((tile[step] + t) * TILE, TILE), TILE)],
                sem.at[slot, t])
            pl.when(t < valid[step])(functools.partial(act, copy))

    @pl.when(c < num[0])
    def _chunk():
        pl.when(c >= 2)(lambda: writes(c - 2, lambda copy: copy.wait()))  # the slot is free again
        lhs = lhs_ref[...]
        rhs = rhs_ref[0].astype(lhs.dtype)
        contract = (((1,), (1,)), ((), ())) if transpose_rhs else (((1,), (0,)), ((), ()))
        result[c % 2] = jax.lax.dot_general(
            lhs, rhs, contract, preferred_element_type=jnp.float32,
            precision=_dot_precision(lhs.dtype),
        ).astype(result.dtype)
        writes(c, lambda copy: copy.start())

    @pl.when(c == num[0] - 1)
    def _drain():
        pl.when(c >= 1)(lambda: writes(c - 1, lambda copy: copy.wait()))
        writes(c, lambda copy: copy.wait())


def _gmm(lhs, rhs, chunks, *, transpose_rhs: bool, interpret: bool, name: str):
    """[M, K] x [G, K, N] -> [M, N], or with ``transpose_rhs`` [M, N] x [G, K, N] -> [M, K]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, inner = lhs.shape
    _, k, n = rhs.shape
    outer = k if transpose_rhs else n
    rows = CHUNK_TILES * TILE
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(chunks[0].shape[0],),
            in_specs=[
                pl.BlockSpec((pl.Element(rows), pl.Element(inner)), _chunk_rows),
                pl.BlockSpec((1, k, n), _chunk_group),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((2, rows, outer), lhs.dtype),
                pltpu.SemaphoreType.DMA((2, CHUNK_TILES)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((m, outer), lhs.dtype),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name=name,
    )(*chunks, lhs, rhs)


def _dw_kernel(group, tile, valid, num, lhs_ref, dout_ref, dw_ref):
    """One chunk's share of its group's lhsᵀ · dout, a tile at a time: only the
    group's own tiles are read (what follows them is another group's rows, or
    nothing)."""
    from jax.experimental import pallas as pl

    c = pl.program_id(0)

    def product(t):
        rows = pl.ds(t * TILE, TILE)
        lhs = lhs_ref[rows, :]
        return jax.lax.dot_general(
            lhs, dout_ref[rows, :], (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=_dot_precision(lhs.dtype),
        )

    def add(t):
        dw_ref[0] += product(t)

    @pl.when(c < num[0])
    def _chunk():
        first = (c == 0) | (group[c] != group[jnp.maximum(c - 1, 0)])

        @pl.when(first)
        def _():
            dw_ref[0] = product(0)

        pl.when(jnp.logical_not(first))(functools.partial(add, 0))
        for t in range(1, CHUNK_TILES):
            pl.when(t < valid[c])(functools.partial(add, t))


def _dw(lhs, dout, chunks, groups: int, *, interpret: bool):
    """[M, K], [M, N] -> [G, K, N] float32: each group's lhsᵀ · dout. A group's
    chunks are consecutive and every group has one, so each block of the result
    is written once: while the next group's chunks are multiplied."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    n = dout.shape[1]
    rows = CHUNK_TILES * TILE
    return pl.pallas_call(
        _dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(chunks[0].shape[0],),
            in_specs=[
                pl.BlockSpec((pl.Element(rows), pl.Element(k)), _chunk_rows),
                pl.BlockSpec((pl.Element(rows), pl.Element(n)), _chunk_rows),
            ],
            out_specs=pl.BlockSpec((1, k, n), _chunk_group),
        ),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), jnp.float32),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="expert_gmm_dw",
    )(*chunks, lhs, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped_kernel(lhs, rhs, chunks, interpret):
    return _gmm(lhs, rhs, chunks, transpose_rhs=False, interpret=interpret, name="expert_gmm_fwd")


def _grouped_kernel_fwd(lhs, rhs, chunks, interpret):
    return _grouped_kernel(lhs, rhs, chunks, interpret), (lhs, rhs, chunks)


def _grouped_kernel_bwd(interpret, res, dout):
    lhs, rhs, chunks = res
    dlhs = _gmm(dout, rhs, chunks, transpose_rhs=True, interpret=interpret, name="expert_gmm_dlhs")
    dw = _dw(lhs, dout, chunks, rhs.shape[0], interpret=interpret)
    return dlhs, dw.astype(rhs.dtype), None


_grouped_kernel.defvjp(_grouped_kernel_fwd, _grouped_kernel_bwd)


def grouped_matmul(
    lhs: jnp.ndarray,
    rhs: jnp.ndarray,
    tile_group: jnp.ndarray,
    num_tiles: jnp.ndarray,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """``lhs`` [M, K] (M a multiple of TILE, tile-aligned by group, zero rows
    as padding) times ``rhs[g]`` [K, N] for the group ``tile_group[i]`` of each
    tile ``i < num_tiles``; rows of later tiles are unspecified. Differentiable
    in ``lhs`` and ``rhs``."""
    if lhs.shape[0] % TILE:
        raise ValueError(f"{lhs.shape[0]} rows are not whole tiles of {TILE}")
    num_tiles = jnp.reshape(num_tiles, (1,)).astype(jnp.int32)
    if _use_kernel(interpret):
        chunks = chunk_plan(tile_group.astype(jnp.int32), num_tiles, rhs.shape[0])
        return _grouped_kernel(lhs, rhs, chunks, bool(interpret))
    # the same products off the chip: every tile in use is TILE rows of its group
    in_use = jnp.arange(tile_group.shape[0]) < num_tiles[0]
    sizes = jnp.zeros((rhs.shape[0],), jnp.int32).at[tile_group].add(jnp.where(in_use, TILE, 0))
    return jax.lax.ragged_dot(
        lhs, rhs.astype(lhs.dtype), sizes, precision=_dot_precision(lhs.dtype),
        preferred_element_type=jnp.float32,
    ).astype(lhs.dtype)
