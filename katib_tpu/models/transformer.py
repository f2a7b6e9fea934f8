"""Decoder-only transformer LM with dp/fsdp/tp/sp sharding — the distributed
flagship workload of the trial runtime.

The reference framework contains no model code (distributed training is
delegated to PyTorchJob/MPIJob trials — SURVEY.md §2.9); this module is the
TPU-native equivalent deliverable: a trial workload that scales over a named
mesh with XLA collectives instead of NCCL/Horovod.

Sharding design (scaling-book recipe — pick a mesh, annotate, let XLA insert
collectives):
- activations: [B, T, E] with B over ('data','fsdp'), T over 'seq';
- attention: heads over 'model' (TP); sequence blocks over 'seq' via ring
  attention (katib_tpu.ops.ring_attention) — long-context first-class;
- params: column-parallel in-projections P(fsdp, model), row-parallel
  out-projections P(model, fsdp) — gradient reduce-scatters ride ICI;
- rotary embeddings are computed from *global* positions so sequence sharding
  is exact.

bfloat16 activations/matmuls with f32 params + optimizer state.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from ..ops.flash_attention import flash_attention, sharded_flash_attention
from ..ops.ring_attention import dense_attention, ring_attention


@dataclasses.dataclass(frozen=True)
class YarnConfig:
    """YaRN's rescaling of rotary frequencies (arXiv:2309.00071): frequencies
    that turn fewer than ``beta_slow`` times over ``original_positions`` are
    divided by ``factor``, those that turn more than ``beta_fast`` times are
    kept, the ones between are blended; cos and sin carry ``attention_factor``."""

    factor: float
    original_positions: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class RotaryConfig:
    theta: float = 10000.0
    fraction: float = 1.0               # share of each head that is rotated: its first dimensions
    yarn: Optional[YarnConfig] = None


@dataclasses.dataclass(frozen=True)
class LayerConfig:
    """What one depth is made of. ``attention``: "full" (causal) or "sliding"
    (causal within ``TransformerConfig.window``); ``mlp``: "dense" (SwiGLU) or
    "routed" (RoutedExperts)."""

    attention: str = "full"
    num_heads: Optional[int] = None     # query heads of this layer; None: TransformerConfig.num_heads
    mlp: str = "dense"


@dataclasses.dataclass(frozen=True)
class RoutedExpertsConfig:
    """Drop-free routing of every token to ``experts_per_token`` of
    ``router_width`` experts, of which this program holds ``num_experts``
    starting at ``first_expert`` (None: all of them). What the experts held
    elsewhere would add is left out: the layer computes its share."""

    router_width: int
    experts_per_token: int
    hidden: int                         # an expert's width
    num_experts: Optional[int] = None
    first_expert: int = 0
    routed_scale: float = 1.0
    shared_hidden: int = 0              # width of the expert every token passes; 0: none

    @property
    def held(self) -> int:
        return self.router_width if self.num_experts is None else self.num_experts


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    embed_dim: int = 512
    num_layers: int = 4
    num_heads: int = 8
    mlp_ratio: int = 4
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    causal: bool = True
    # Mixture-of-experts: 0 = dense MLP in every block; otherwise every block
    # uses a top-1 routed MoE with experts sharded over the mesh 'expert' axis.
    num_experts: int = 0
    expert_capacity_factor: float = 2.0
    moe_aux_weight: float = 1e-2
    # What follows is None/empty for the decoder above: one kind of layer,
    # multi-head attention, heads of embed_dim / num_heads, a tied head.
    head_size: Optional[int] = None     # stated head size (num_heads * head_size need not be embed_dim)
    num_kv_heads: Optional[int] = None  # grouped KV heads; None: one per query head
    mlp_hidden: Optional[int] = None    # the dense SwiGLU's width; None: mlp_ratio * embed_dim
    window: Optional[int] = None        # keys a "sliding" layer's query sees, itself included
    layers: Tuple[LayerConfig, ...] = ()                # by depth; empty: LayerConfig() at every depth
    rotary: Tuple[Tuple[str, RotaryConfig], ...] = ()   # by attention kind; a kind left out: RotaryConfig()
    attention_gate: bool = False        # a per-head sigmoid gate on the attention output
    tied_head: bool = True
    routed: Optional[RoutedExpertsConfig] = None        # the "routed" layers' experts
    rms_eps: float = 1e-6

    @property
    def head_dim(self) -> int:
        return self.head_size or self.embed_dim // self.num_heads

    def layer(self, depth: int) -> LayerConfig:
        return self.layers[depth] if self.layers else LayerConfig()

    def rotary_of(self, kind: str) -> RotaryConfig:
        return dict(self.rotary).get(kind, RotaryConfig())


def rotary_frequencies(rotated: int, theta: float, yarn: YarnConfig) -> np.ndarray:
    """YaRN's ``rotated // 2`` frequencies, as the published modelling code
    computes them (transformers ``_compute_yarn_parameters``)."""
    half = rotated // 2
    turns = theta ** (np.arange(half, dtype=np.float64) * 2.0 / rotated)

    def correction(rotations: float) -> float:
        return rotated * math.log(yarn.original_positions / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction(yarn.beta_fast)), 0)
    high = min(math.ceil(correction(yarn.beta_slow)), rotated - 1)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return ((1.0 / (yarn.factor * turns)) * ramp + (1.0 / turns) * (1.0 - ramp)).astype(np.float32)


def rotary_embed(
    x: jnp.ndarray,
    positions: jnp.ndarray,
    theta: float = 10000.0,
    fraction: float = 1.0,
    yarn: Optional[YarnConfig] = None,
) -> jnp.ndarray:
    """RoPE on [B, T, H, D] with explicit global positions [B, T]: the first
    ``fraction`` of D is rotated (its two halves are the pairs), the rest is
    passed through."""
    d = x.shape[-1]
    rotated = int(d * fraction)
    half = rotated // 2
    if yarn is None:
        freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32) * (math.log(theta) / half))
        scale = 1.0
    else:
        freqs = jnp.asarray(rotary_frequencies(rotated, theta, yarn))
        scale = yarn.attention_factor
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, T, half]
    sin = jnp.sin(angles)[:, :, None, :]
    cos = jnp.cos(angles)[:, :, None, :]
    if scale != 1.0:
        sin, cos = sin * scale, cos * scale
    sin, cos = sin.astype(x.dtype), cos.astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:rotated]
    parts = [x1 * cos - x2 * sin, x1 * sin + x2 * cos]
    if rotated < d:
        parts.append(x[..., rotated:])
    return jnp.concatenate(parts, axis=-1)


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        return (x * jax.lax.rsqrt(var + self.eps)).astype(x.dtype) * scale


def _lecun_normal_drawn_flat(key, shape, dtype):
    """nn.DenseGeneral's kernel initialiser: LeCun normal drawn at
    [in, all features flattened], then reshaped."""
    flat = nn.initializers.lecun_normal()(key, (shape[0], math.prod(shape[1:])), dtype)
    return flat.reshape(shape)


class QKVProjection(nn.Module):
    """q, k and v as three products of x with the [E, H, D] slices of one
    stored kernel [E, 3, H, D] (the parameter nn.DenseGeneral((3, h, d))
    would create: same name, shape, dtype and initial values).

    One DenseGeneral whose result is sliced computes the same numbers, but
    XLA folds the slices and the [3, H] feature dimensions into a 5-D
    convolution for the weight gradient, which runs at a third of the MXU's
    rate on a v5e (PERF.md, PR 28); three plain products do not."""

    num_heads: int
    head_dim: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", _lecun_normal_drawn_flat,
            (x.shape[-1], 3, self.num_heads, self.head_dim), jnp.float32,
        )
        x, kernel = nn.dtypes.promote_dtype(x, kernel, dtype=self.dtype)
        return tuple(jnp.einsum("...e,ehd->...hd", x, kernel[:, i]) for i in range(3))


@jax.custom_vjp
def _gradient_in_stored_order(kernel):
    """The identity; the gradient that comes back through it is laid out
    row-major, as a stored kernel is (GroupedQKVProjection says what for)."""
    return kernel


def _gradient_in_stored_order_bwd(_, g):
    return (with_layout_constraint(g, Layout(major_to_minor=tuple(range(g.ndim)))),)


_gradient_in_stored_order.defvjp(lambda kernel: (kernel, None), _gradient_in_stored_order_bwd)


class HeadProjection(nn.Module):
    """x [..., E] times one stored kernel [E, H, D]: the parameter
    nn.DenseGeneral((h, d)) creates (same name, shape, dtype and initial
    values), its value and its gradients."""

    num_heads: int
    head_dim: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", _lecun_normal_drawn_flat,
            (x.shape[-1], self.num_heads, self.head_dim), jnp.float32,
        )
        x, kernel = nn.dtypes.promote_dtype(x, kernel, dtype=self.dtype)
        return jnp.einsum("...e,ehd->...hd", x, _gradient_in_stored_order(kernel))


class GroupedQKVProjection(nn.Module):
    """q over ``num_heads`` heads, k and v over ``num_kv_heads``: three stored
    kernels [E, H, D], three plain products (grouped KV heads have no
    [E, 3, H, D]).

    Left to nn.DenseGeneral, XLA computes q's weight gradient head-major
    (``[E, H, D]{2,0,1}``: the flash kernels hand the heads back as
    ``[H, T, D]``) in one fusion with AdamW, which so reads the kernel and
    both moments through transposing copies and writes all three head-major,
    for three more copies behind it: 21.0 ms a step in the sparse cell where
    the out kernel's update, the same arithmetic over the same bytes, takes
    8.4. However the product is written, XLA folds it back into that fusion.
    With the gradient's layout stated, the product is a fusion of its own
    and the turn is made on its bfloat16 result alone, inside AdamW's fusion,
    which runs over plain layouts of kernel and moments: 10.2 ms a step
    (PERF.md, PR 33)."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        def project(name, heads):
            return HeadProjection(heads, self.head_dim, dtype=self.dtype, name=name)(x)

        return project("q", self.num_heads), project("k", self.num_kv_heads), project(
            "v", self.num_kv_heads)


class Attention(nn.Module):
    config: TransformerConfig
    mesh: Optional[Any] = None
    # Set when this module is traced INSIDE a shard_map that is manual over
    # a sequence axis (pipeline stages with sequence parallelism): attention
    # runs the ring schedule directly over that axis instead of wrapping its
    # own shard_map. positions must be GLOBAL (caller offsets by rank).
    seq_axis: Optional[str] = None
    layer: LayerConfig = LayerConfig()

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        h, d = self.layer.num_heads or cfg.num_heads, cfg.head_dim
        kv = cfg.num_kv_heads or h
        if self.layer.attention not in ("full", "sliding"):
            raise ValueError(f"no attention of the kind {self.layer.attention!r}")
        window = cfg.window if self.layer.attention == "sliding" else None
        if self.layer.attention == "sliding" and not window:
            raise ValueError("a sliding layer needs TransformerConfig.window")
        if kv == h:
            q, k, v = QKVProjection(h, d, dtype=cfg.dtype, name="qkv")(x)
        else:
            q, k, v = GroupedQKVProjection(h, kv, d, dtype=cfg.dtype, name="qkv")(x)
        rotary = cfg.rotary_of(self.layer.attention)
        q = rotary_embed(q, positions, rotary.theta, rotary.fraction, rotary.yarn)
        k = rotary_embed(k, positions, rotary.theta, rotary.fraction, rotary.yarn)
        ring_over_mesh = self.mesh is not None and _mesh_axis_size(self.mesh, "seq") > 1
        if (self.seq_axis is not None or ring_over_mesh) and (kv != h or window is not None):
            raise NotImplementedError(
                "ring attention over a sequence axis takes neither grouped KV heads nor a window")
        if self.seq_axis is not None:
            from ..ops.ring_attention import ring_attention_local

            o = ring_attention_local(q, k, v, self.seq_axis, causal=cfg.causal)
        elif self.mesh is not None:
            # 'expert' is a batch axis here: outside the MoE layers it acts
            # as pure data parallelism (see parallel.mesh.activation_batch_axes)
            batch_axes = ("data", "fsdp", "expert")
            if ring_over_mesh:
                # cross-device sequence blocks: ring schedule over ppermute
                o = ring_attention(
                    q, k, v, self.mesh, causal=cfg.causal, batch_axes=batch_axes
                )
            else:
                # seq unsharded: fused Pallas flash kernel per local shard
                o = sharded_flash_attention(
                    q, k, v, self.mesh, causal=cfg.causal, batch_axes=batch_axes,
                    **({} if window is None else {"window": window}),
                )
        else:
            o = flash_attention(q, k, v, causal=cfg.causal, window=window)
        if cfg.attention_gate:
            # one sigmoid gate a head, from the layer's input, scores in float32
            gate = nn.Dense(h, use_bias=False, dtype=jnp.float32, name="gate")(
                x.astype(jnp.float32))
            o = o * jax.nn.sigmoid(gate)[..., None].astype(o.dtype)
        return nn.DenseGeneral(
            cfg.embed_dim, axis=(-2, -1), use_bias=False, dtype=cfg.dtype, name="out"
        )(o)


def _mesh_axis_size(mesh, axis: str) -> int:
    from ..parallel.mesh import mesh_axis_sizes

    return mesh_axis_sizes(mesh).get(axis, 1)


class MLP(nn.Module):
    config: TransformerConfig
    hidden: Optional[int] = None  # None: the configuration's dense width

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        hidden = self.hidden or cfg.mlp_hidden or cfg.embed_dim * cfg.mlp_ratio
        up = nn.Dense(hidden, use_bias=False, dtype=cfg.dtype, name="up")(x)
        gate = nn.Dense(hidden, use_bias=False, dtype=cfg.dtype, name="gate")(x)
        return nn.Dense(cfg.embed_dim, use_bias=False, dtype=cfg.dtype, name="down")(
            nn.silu(gate) * up
        )


# ---------------------------------------------------------------------------
# Routed experts: drop-free top-k over a share of the experts
# ---------------------------------------------------------------------------

def route(scores: jnp.ndarray, routed: RoutedExpertsConfig):
    """``scores`` [N, router_width], float32 in (0, 1). Returns ``weights``
    [N, k] float32 — the k largest scores of each token, normalised to sum 1
    and scaled —, ``local`` [N, k] int32: the chosen experts counted from the
    first one held here, ``routed.held`` for one held elsewhere, and ``chosen``
    [N, k]: the experts by the router's own numbering."""
    top, chosen = jax.lax.top_k(scores, routed.experts_per_token)
    weights = routed.routed_scale * top / jnp.sum(top, axis=-1, keepdims=True)
    local = chosen - routed.first_expert
    local = jnp.where((local >= 0) & (local < routed.held), local, routed.held)
    return weights, local.astype(jnp.int32), chosen


def dispatch_plan(local: jnp.ndarray, held: int, tile: int, spare: int = 0):
    """Where every assignment goes in the tile-aligned buffer that the grouped
    products read (ops/grouped_matmul.py): tokens sorted by expert, every
    expert's rows starting on a tile and at least one tile long.

    ``local`` [N, k] as ``route`` gives it. The buffer has
    ``(ceil(N k / tile) + held + spare) * tile`` rows, enough for any routing
    with ``spare`` tiles never in use at its end: no assignment is ever
    dropped. Returns a dict of
    ``dest`` [N, k]: an assignment's row (0 where ``landed`` is false),
    ``landed`` [N, k]: it is to an expert held here,
    ``source`` [M]: the flat assignment ``t * k + j`` a row holds (N k: none),
    ``tile_group`` [M / tile], ``num_tiles`` (), ``load`` [held]."""
    n, k = local.shape
    flat = local.reshape(-1)
    landed = flat < held
    onehot = (flat[:, None] == jnp.arange(held, dtype=jnp.int32)[None, :]).astype(jnp.int32)
    load = jnp.sum(onehot, axis=0)                                   # [held]
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=1)  # place among its expert's
    tiles_of = jnp.maximum((load + tile - 1) // tile, 1)
    first_tile = jnp.cumsum(tiles_of) - tiles_of                     # [held]
    num_tiles = jnp.sum(tiles_of)
    safe = jnp.minimum(flat, held - 1)
    dest = jnp.where(landed, first_tile[safe] * tile + rank, 0)

    tiles = -(-n * k // tile) + held + spare
    # tile i's expert: the last one whose first tile is not after i
    tile_group = jnp.sum(
        jnp.arange(tiles, dtype=jnp.int32)[:, None] >= first_tile[None, :], axis=1) - 1
    tile_group = jnp.clip(tile_group, 0, held - 1).astype(jnp.int32)
    # row r of expert g holds the (r - first row of g)-th of g's assignments in
    # token order: position first_sorted[g] + that in the stable sort by expert
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    first_sorted = jnp.cumsum(load) - load
    rows = jnp.arange(tiles * tile, dtype=jnp.int32)
    group = tile_group[rows // tile]
    within = rows - first_tile[group] * tile
    filled = (within < load[group]) & (rows // tile < num_tiles)
    source = jnp.where(filled, order[jnp.minimum(first_sorted[group] + within, n * k - 1)], n * k)
    return {
        "dest": dest.reshape(n, k).astype(jnp.int32), "landed": landed.reshape(n, k),
        "source": source.astype(jnp.int32), "tile_group": tile_group,
        "num_tiles": num_tiles.astype(jnp.int32), "load": load,
    }


@jax.custom_vjp
def gather_rows(x, index, valid, readers, readers_valid):
    """``y[r] = x[index[r]]`` where ``valid[r]``, else 0. ``readers``
    [rows of x, fan] names, for each row of ``x``, the rows of ``y`` that read
    it (``readers_valid`` says which entries count), so the gradient is a
    gather too: a scatter-add over tens of thousands of rows costs many times
    the gather on a TPU.

    Where each mask sits. Forward: on the index — a row that is not valid
    reads one zero row appended to ``x``, so its zeros are exact and no select
    passes over the ``[rows of y, E]`` result. Backward: on the gathered
    ``[rows of x, fan, E]`` values, as a select — the rows of ``dy`` may be
    ones nothing ever wrote (the dispatch's ``dy`` comes out of the grouped
    kernels), and a product with 0 would let a NaN there through."""
    zero_row = jnp.zeros((1,) + x.shape[1:], x.dtype)
    index = jnp.where(valid, index, x.shape[0])
    return jnp.take(jnp.concatenate([x, zero_row]), index, axis=0, mode="clip")


def _gather_rows_fwd(x, index, valid, readers, readers_valid):
    return gather_rows(x, index, valid, readers, readers_valid), (readers, readers_valid)


def _gather_rows_bwd(res, dy):
    readers, readers_valid = res
    read = jnp.take(dy, readers, axis=0, mode="clip")               # [rows of x, fan, E]
    dx = jnp.sum(
        jnp.where(readers_valid[..., None], read, 0).astype(jnp.float32), axis=1)
    return dx.astype(dy.dtype), None, None, None, None


gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


@jax.custom_vjp
def combine_rows(out, weights, dest, landed, source, filled):
    """``y[t] = sum_j weights[t, j] * out[dest[t, j]]`` over the assignments
    that ``landed``: what a routed layer hands back, float32 sums returned in
    ``out``'s dtype. ``out`` [M, E] is the buffer ``dispatch_plan`` lays out,
    ``weights`` [N, k] float32; ``dest``, ``landed`` [N, k] and ``source`` [M]
    are the plan's, ``filled`` [M] says which rows hold an assignment.

    Which rows may be unwritten: those of tiles at or past ``num_tiles`` hold
    whatever the buffer held (ops/grouped_matmul.py), so a row reaches a
    result only through a select on scalars, never through a product with 0.
    Forward: the mask is on the ``[N, k]`` weights; an assignment that did not
    land has ``dest`` 0, and row 0 is finite (tile 0 is always in use, its
    padding rows are zero). No select passes over ``[N k, E]``.

    The gradient is its own, buffer-major, so that neither pass keeps or makes
    again the ``[N k, E]`` gather: one row gather of ``dy`` over the buffer's
    M rows, ``d_out`` its rows times a weight a row (0 where not ``filled``,
    chosen on the M scalars), ``d_weights`` a row-wise dot over the buffer
    gathered as N k scalars (0 where not ``landed``)."""
    n, k = weights.shape
    back = jnp.take(out, dest.reshape(-1), axis=0, mode="clip").reshape(n, k, -1)
    w = jnp.where(landed, weights, 0.0)
    return jnp.sum(w[..., None] * back.astype(jnp.float32), axis=1).astype(out.dtype)


def _combine_rows_fwd(out, weights, dest, landed, source, filled):
    return combine_rows(out, weights, dest, landed, source, filled), (
        out, weights, dest, landed, source, filled)


def _combine_rows_bwd(res, dy):
    out, weights, dest, landed, source, filled = res
    k = weights.shape[1]
    # an unfilled row's source is N k: it reads the last token's dy, a finite row
    g = jnp.take(dy, source // k, axis=0, mode="clip").astype(jnp.float32)        # [M, E]
    w_row = jnp.where(filled, jnp.take(weights.reshape(-1), source, mode="clip"), 0.0)
    d_out = (w_row[:, None] * g).astype(out.dtype)
    dots = jnp.sum(g * out.astype(jnp.float32), axis=1)                            # [M]; anything where unwritten
    d_weights = jnp.where(landed, jnp.take(dots, dest, mode="clip"), 0.0)
    return d_out, d_weights.astype(weights.dtype), None, None, None, None


combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


class RoutedExperts(nn.Module):
    """The experts of a "routed" layer that this program holds, and the expert
    every token passes.

    Router scores are sigmoid, float32; each token goes to its
    ``experts_per_token`` best of ``router_width``, with weights normalised
    over the chosen and scaled. No capacity: every assignment to an expert held
    here is computed, whatever the load (``dispatch_plan``); assignments to
    experts held elsewhere add nothing here. On a mesh with an 'expert' axis
    this is the same layer told another share; no exchange is part of it.

    Around the grouped products the rows move once each way and every mask is
    on scalars. The dispatch (``gather_rows``) fills the tile-aligned buffer:
    a row that pads an expert out, or lies on a tile past ``num_tiles``, reads
    one zero row appended to the tokens, so the rows the kernels read are
    zero where the layout says so, to the bit. The kernels never write the
    rows of tiles at or past ``num_tiles``: in ``hidden``, ``out`` and the
    gradient that comes back to the dispatch those rows hold anything. The
    combine (``combine_rows``) masks on the ``[N, k]`` weights and lets an
    assignment that did not land read row 0, a finite row; its gradient masks
    on a weight a row and on the gathered dots. Only the dispatch's gradient
    selects over gathered rows, ``[N, k, E]``: it reads what the kernels left.

    Sows ``routing`` under 'intermediates': the assignments that landed here,
    the largest and the mean load of a held expert (and ``chosen``, the experts
    each token chose, for who wants to compare selections)."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x):
        from ..ops.grouped_matmul import CHUNK_TILES, TILE, grouped_matmul

        cfg, routed = self.config, self.config.routed
        b, t, e = x.shape
        n, k, held, f = b * t, routed.experts_per_token, routed.held, routed.hidden
        tokens = x.reshape(n, e)

        logits = nn.Dense(
            routed.router_width, use_bias=False, dtype=jnp.float32,
            precision=jax.lax.Precision.HIGHEST, name="router",
        )(tokens.astype(jnp.float32))
        weights, local, chosen = route(jax.nn.sigmoid(logits), routed)
        plan = dispatch_plan(local, held, TILE, spare=CHUNK_TILES - 1)
        self.sow("intermediates", "routing", {
            "landed": jnp.sum(plan["landed"]).astype(jnp.float32),
            "load_max": jnp.max(plan["load"]).astype(jnp.float32),
            "load_mean": jnp.mean(plan["load"].astype(jnp.float32)),
            "chosen": chosen,
        })

        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1, batch_axis=(0,))
        w_gate = self.param("gate", init, (held, e, f), jnp.float32)
        w_up = self.param("up", init, (held, e, f), jnp.float32)
        w_down = self.param("down", init, (held, f, e), jnp.float32)

        @jax.checkpoint  # a layer's buffers are for N k rows: kept for one layer at a time
        def experts(tokens, weights, w_gate, w_up, w_down):
            tokens = tokens.astype(cfg.dtype)
            source, dest, landed = plan["source"], plan["dest"], plan["landed"]
            filled = source < n * k
            rows = gather_rows(tokens, source // k, filled, dest, landed)
            products = functools.partial(
                grouped_matmul, tile_group=plan["tile_group"], num_tiles=plan["num_tiles"])
            hidden = nn.silu(products(rows, w_gate)) * products(rows, w_up)
            out = products(hidden, w_down)
            return combine_rows(out, weights, dest, landed, source, filled)

        y = experts(tokens, weights, w_gate, w_up, w_down).reshape(b, t, e)
        if routed.shared_hidden:
            y = y + MLP(cfg, hidden=routed.shared_hidden, name="shared")(x)
        return y


class MoE(nn.Module):
    """Top-1 routed mixture-of-experts FFN (Switch style) with experts laid
    out over the mesh 'expert' axis.

    Expert parallelism, TPU-native: per-expert FFN weights are [X, E, F]
    sharded P('expert', 'fsdp', 'model'); the dispatched token buffer
    [B, X, C, E] carries a sharding constraint that puts X on 'expert', so XLA
    inserts the token all-to-all over ICI (the reference delegates any such
    layout to trial-image NCCL — SURVEY.md §2.9). A load-balance aux loss is
    sown under 'intermediates'/'moe_aux_loss' for the train step to collect.
    """

    config: TransformerConfig
    mesh: Optional[Any] = None
    # Set when traced INSIDE a shard_map already manual over an expert axis
    # (pipeline stages with expert parallelism): the module's FFN weights
    # are created at their LOCAL shard shape [X/ep, E, F] and the token
    # exchange is a direct all_to_all over the axis — no nested shard_map.
    expert_axis: Optional[str] = None
    expert_axis_size: int = 1

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        b, t, e = x.shape
        nx = cfg.num_experts
        hidden = cfg.embed_dim * cfg.mlp_ratio
        capacity = max(1, int(cfg.expert_capacity_factor * t / nx))

        router_logits = nn.Dense(nx, use_bias=False, dtype=jnp.float32, name="router")(
            x.astype(jnp.float32)
        )  # [B, T, X]
        probs = jax.nn.softmax(router_logits, axis=-1)
        gate = jnp.max(probs, axis=-1)          # [B, T]
        expert_idx = jnp.argmax(probs, axis=-1)  # [B, T]

        onehot = jax.nn.one_hot(expert_idx, nx, dtype=jnp.float32)  # [B, T, X]
        # position of each token within its expert's buffer, per batch row
        pos = jnp.cumsum(onehot, axis=1) * onehot - 1.0             # [B, T, X]
        keep = (pos >= 0) & (pos < capacity)
        dispatch = onehot[..., None] * jax.nn.one_hot(
            jnp.clip(pos, 0, capacity - 1).astype(jnp.int32), capacity, dtype=jnp.float32
        )  # [B, T, X, C]
        dispatch = jnp.where(keep[..., None], dispatch, 0.0)
        combine = dispatch * gate[:, :, None, None]

        # load balance: fraction of tokens per expert vs mean router prob
        frac_tokens = jnp.mean(onehot, axis=(0, 1))
        frac_probs = jnp.mean(probs, axis=(0, 1))
        aux = cfg.moe_aux_weight * nx * jnp.sum(frac_tokens * frac_probs)
        self.sow("intermediates", "moe_aux_loss", aux)

        # in the manual (in-pipeline) mode the FFN weights live at their
        # LOCAL shard shape — the stage's shard_map in_specs put 'expert'
        # on the X dim, so each device holds nx/ep experts
        nx_local = nx
        if self.expert_axis is not None:
            assert nx % self.expert_axis_size == 0, (
                f"num_experts {nx} not divisible by expert axis "
                f"{self.expert_axis_size}"
            )
            nx_local = nx // self.expert_axis_size
        w_in = self.param(
            "w_in", nn.initializers.lecun_normal(), (nx_local, e, hidden), jnp.float32
        )
        w_gate = self.param(
            "w_gate", nn.initializers.lecun_normal(), (nx_local, e, hidden), jnp.float32
        )
        w_out = self.param(
            "w_out", nn.initializers.lecun_normal(), (nx_local, hidden, e), jnp.float32
        )
        if self.mesh is not None:
            # ZeRO idiom (as for the embed table): expert weights are STORED
            # with 'fsdp' on the embed dim but COMPUTED gathered — otherwise
            # the FFN einsums propagate embed-dim-over-'fsdp' onto the
            # activations, which can't meet the batch-sharded residual layout
            # without an involuntary full rematerialization. 'expert' and
            # 'model' stay sharded at compute time.
            from jax.sharding import NamedSharding, PartitionSpec as P

            w_in = jax.lax.with_sharding_constraint(
                w_in, NamedSharding(self.mesh, P("expert", None, "model"))
            )
            w_gate = jax.lax.with_sharding_constraint(
                w_gate, NamedSharding(self.mesh, P("expert", None, "model"))
            )
            w_out = jax.lax.with_sharding_constraint(
                w_out, NamedSharding(self.mesh, P("expert", "model", None))
            )

        ep = bp = 1
        if self.mesh is not None:
            from ..parallel.mesh import mesh_axis_sizes

            sizes = mesh_axis_sizes(self.mesh)
            ep = sizes.get("expert", 1)
            bp = ep * sizes.get("data", 1) * sizes.get("fsdp", 1)

        def _ffn(expert_in, w_in, w_gate, w_out):
            h = jnp.einsum("bxce,xef->bxcf", expert_in, w_in.astype(cfg.dtype))
            g = jnp.einsum("bxce,xef->bxcf", expert_in, w_gate.astype(cfg.dtype))
            return jnp.einsum(
                "bxcf,xfe->bxce", nn.silu(g) * h, w_out.astype(cfg.dtype)
            )

        def _a2a_dispatch_ffn_combine(dispatch, combine, x, w_in, w_gate, w_out, axis):
            expert_in = jnp.einsum(
                "btxc,bte->bxce", dispatch.astype(cfg.dtype), x
            )  # [b_local, X, C, E]
            expert_in = jax.lax.all_to_all(
                expert_in, axis, split_axis=1, concat_axis=0, tiled=True
            )  # [b_local·ep, X/ep, C, E] — each device holds ITS experts' tokens
            out = _ffn(expert_in, w_in, w_gate, w_out)
            out = jax.lax.all_to_all(
                out, axis, split_axis=0, concat_axis=1, tiled=True
            )  # [b_local, X, C, E] — tokens return to their batch shard
            return jnp.einsum("btxc,bxce->bte", combine.astype(cfg.dtype), out)

        if self.expert_axis is not None and self.expert_axis_size > 1:
            # already inside a manual shard_map (pipeline stage): exchange
            # tokens directly over the axis, weights are pre-sharded
            return _a2a_dispatch_ffn_combine(
                dispatch, combine, x, w_in, w_gate, w_out, self.expert_axis
            )

        if ep > 1 and nx % ep == 0 and b % bp == 0:
            # Explicit expert parallelism: tokens arrive batch-sharded over
            # data×fsdp×expert (activation_batch_axes), each device builds
            # its batch shard's dispatch buffer locally, and ONE tiled
            # all_to_all per direction exchanges batch-shards for
            # expert-shards over the ICI 'expert' axis — where GSPMD's
            # fallback lowering (all-gather + slice) moves ep× the bytes and
            # replicates the FFN compute. The batch axes are manual so the
            # body stays batch-sharded end to end; only 'model' (TP on the
            # expert FFN matmuls) remains a GSPMD-auto axis.
            from jax.sharding import PartitionSpec as P

            def dispatch_ffn_combine(dispatch, combine, x, w_in, w_gate, w_out):
                return _a2a_dispatch_ffn_combine(
                    dispatch, combine, x, w_in, w_gate, w_out, "expert"
                )

            batch_axes = ("data", "fsdp", "expert")
            ein_spec = P(batch_axes, None, None, None)
            w_spec = P("expert", None, None)  # replicated over data/fsdp,
            fn = jax.shard_map(                   # 'model' TP stays auto
                dispatch_ffn_combine,
                mesh=self.mesh,
                in_specs=(ein_spec, ein_spec, P(batch_axes, None, None),
                          w_spec, w_spec, w_spec),
                out_specs=P(batch_axes, None, None),
                check_vma=False,
                axis_names={"data", "fsdp", "expert"},
            )
            # jit wrapper: a partial-manual shard_map (axis_names ⊂ mesh
            # axes) only traces under jit; the wrapper inlines when the
            # caller is already jitted and makes eager apply/init work too.
            # Always reached under the caller's jit trace in the train path,
            # so the fresh wrapper is traced once per outer compile — not a
            # per-step recompile; only repeated EAGER calls would re-trace.
            return jax.jit(fn)(dispatch, combine, x, w_in, w_gate, w_out)  # katib-check: ignore[KTC105] inlined under the caller's jit

        expert_in = jnp.einsum(
            "btxc,bte->bxce", dispatch.astype(cfg.dtype), x
        )  # [B, X, C, E]
        out = _ffn(expert_in, w_in, w_gate, w_out)
        return jnp.einsum("btxc,bxce->bte", combine.astype(cfg.dtype), out)


def collect_moe_aux(mutated) -> jnp.ndarray:
    """Sum every sown 'moe_aux_loss' leaf from a ``mutable=['intermediates']``
    apply result — the one place the sow key is interpreted (used by both
    the jit train step and the pipeline's stage loop)."""
    import flax

    flat = flax.traverse_util.flatten_dict(mutated.get("intermediates", {}))
    return jnp.float32(
        sum(jnp.sum(jnp.asarray(v)) for k, v in flat.items() if "moe_aux_loss" in k)
    )


def collect_routing(mutated) -> Dict[str, jnp.ndarray]:
    """The counters every RoutedExperts layer sowed, summed over the layers:
    ``landed`` (assignments to experts held here), ``load_max`` and
    ``load_mean`` (the largest and the mean load of a held expert)."""
    import flax

    flat = flax.traverse_util.flatten_dict(mutated.get("intermediates", {}))
    total: Dict[str, jnp.ndarray] = {}
    for path, sown in flat.items():
        if path[-1] == "routing":  # a tuple: one entry a call of the layer
            for counters in sown:
                for name, value in counters.items():
                    if name != "chosen":
                        total[name] = total.get(name, 0.0) + value
    return total


def _pin_residual(x, mesh):
    """Pin the residual stream [B, T, E] to its canonical layout (batch over
    'data'/'fsdp', sequence over 'seq', embed replicated).

    Without this, GSPMD propagates layouts *through* the residual adds — e.g.
    the MoE dispatch's batch-over-'expert' sharding meets ring attention's
    seq-sharded shard_map boundary and the partitioner falls back to an
    involuntary full rematerialization (replicate, then re-partition) of the
    activation every step. An explicit constraint at each block boundary
    keeps every transition a cheap all-to-all/collective-permute."""
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.mesh import activation_batch_axes, mesh_axis_sizes

    sizes = mesh_axis_sizes(mesh)
    b, t, _ = x.shape
    batch_axes = activation_batch_axes(sizes, b) or None
    seq_axis = "seq" if sizes.get("seq", 1) > 1 and t % sizes["seq"] == 0 else None
    if batch_axes is None and seq_axis is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(batch_axes, seq_axis, None))
    )


class Block(nn.Module):
    config: TransformerConfig
    mesh: Optional[Any] = None
    seq_axis: Optional[str] = None      # see Attention.seq_axis
    expert_axis: Optional[str] = None   # see MoE.expert_axis
    expert_axis_size: int = 1
    layer: LayerConfig = LayerConfig()  # what this depth is made of

    @nn.compact
    def __call__(self, x, positions):
        eps = self.config.rms_eps
        x = _pin_residual(
            x + Attention(self.config, self.mesh, self.seq_axis, self.layer, name="attn")(
                RMSNorm(eps, name="ln1")(x), positions
            ),
            self.mesh,
        )
        if self.layer.mlp == "routed":
            x = x + RoutedExperts(self.config, name="experts")(RMSNorm(eps, name="ln2")(x))
        elif self.layer.mlp != "dense":
            raise ValueError(f"no MLP of the kind {self.layer.mlp!r}")
        elif self.config.num_experts > 0:
            x = x + MoE(
                self.config, self.mesh, self.expert_axis, self.expert_axis_size,
                name="moe",
            )(RMSNorm(eps, name="ln2")(x))
        else:
            x = x + MLP(self.config, name="mlp")(RMSNorm(eps, name="ln2")(x))
        return _pin_residual(x, self.mesh)


class TransformerLM(nn.Module):
    config: TransformerConfig
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, tokens, positions=None):
        cfg = self.config
        if positions is None:
            b, t = tokens.shape
            positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        emb = self.param(
            "embed", nn.initializers.normal(0.02), (cfg.vocab_size, cfg.embed_dim), jnp.float32
        )
        if self.mesh is not None:
            # ZeRO idiom: the table is STORED sharded over 'fsdp'
            # (param_sharding_rules) but COMPUTED replicated — one cheap
            # [V, E] all-gather here instead of the involuntary full
            # rematerialization the partitioner otherwise emits for the
            # token-gather forward (and its scatter-add transpose), whose
            # activations can't transition from embed-dim-sharded to
            # batch-sharded efficiently.
            from jax.sharding import NamedSharding, PartitionSpec as P

            emb = jax.lax.with_sharding_constraint(
                emb, NamedSharding(self.mesh, P(None, None))
            )
        x = _pin_residual(emb[tokens].astype(cfg.dtype), self.mesh)
        for i in range(cfg.num_layers):
            x = Block(cfg, self.mesh, layer=cfg.layer(i), name=f"block{i}")(x, positions)
        x = RMSNorm(cfg.rms_eps, name="ln_f")(x)
        # the output head, tied to the embedding or its own [V, E] — the
        # largest matmul in the model: bf16 operands at native MXU rate, f32
        # accumulation for the softmax/loss
        head = emb if cfg.tied_head else self.param(
            "head", nn.initializers.normal(0.02), (cfg.vocab_size, cfg.embed_dim), jnp.float32
        )
        logits = jnp.einsum(
            "bte,ve->btv", x.astype(cfg.dtype), head.astype(cfg.dtype),
            preferred_element_type=jnp.float32,
        )
        return logits


def abstract_lm_program(assignments: Dict[str, str]):
    """Abstract program probe (katib_tpu.analysis.program) for the LM trial
    (parallel/train.py:run_lm_trial): the canonical jitted train step traced
    from ShapeDtypeStruct avals — eval_shape init, no mesh, no devices.

    learning_rate enters as a traced f32 scalar (runtime-scalar); every
    architecture/shape knob (embed_dim, num_layers, num_heads, batch_size,
    seq_len, vocab_size) changes avals, and the parallelism degrees select
    a different sharded program, so all of those are fingerprint material
    (shape-affecting); num_steps/profile are host-side knobs."""
    from ..analysis.program import ProgramProbe

    config = TransformerConfig(
        vocab_size=int(assignments.get("vocab_size", "512")),
        embed_dim=int(assignments.get("embed_dim", "128")),
        num_layers=int(assignments.get("num_layers", "2")),
        num_heads=int(assignments.get("num_heads", "4")),
        max_seq_len=int(assignments.get("seq_len", "128")),
    )
    batch = int(assignments.get("batch_size", "8"))
    seq = int(assignments.get("seq_len", "128"))
    model = TransformerLM(config)  # mesh-free abstract twin; the mesh
    # layout enters the fingerprint through `statics` below instead
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    targets = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    lr = jax.ShapeDtypeStruct((), jnp.float32)
    params = jax.eval_shape(
        lambda r, t: model.init(r, t)["params"], rng, tokens
    )

    def train_step(params, lr, tokens, targets):
        def loss_fn(p):
            logits = model.apply({"params": p}, tokens)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
            return nll.mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        params = jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)
        return params, loss

    return ProgramProbe(
        fn=train_step,
        args=(params, lr, tokens, targets),
        params=params,
        hyperparams={"learning_rate": lr},
        host_params={"num_steps", "profile"},
        statics={
            "tensor_parallel": int(assignments.get("tensor_parallel", "1")),
            "sequence_parallel": int(assignments.get("sequence_parallel", "1")),
        },
    )


# ---------------------------------------------------------------------------
# Sharding rules
# ---------------------------------------------------------------------------

def param_sharding_rules(path: Tuple[str, ...]):
    """Param-tree path -> PartitionSpec (TP column/row split + fsdp)."""
    from jax.sharding import PartitionSpec as P

    name = "/".join(path)
    if "qkv/kernel" in name:
        return P("fsdp", None, "model", None)     # [E, 3, H, D]
    if "qkv/q/kernel" in name or "qkv/k/kernel" in name or "qkv/v/kernel" in name:
        return P("fsdp", "model", None)           # [E, H or KV, D]
    if "attn/out/kernel" in name:
        return P("model", None, "fsdp")           # [H, D, E]
    if "up/kernel" in name or "gate/kernel" in name:
        return P("fsdp", "model")                 # [E, F]
    if "down/kernel" in name:
        return P("model", "fsdp")                 # [F, E]
    if "moe/w_in" in name or "moe/w_gate" in name:
        return P("expert", "fsdp", "model")       # [X, E, F]
    if "moe/w_out" in name:
        return P("expert", "model", "fsdp")       # [X, F, E]
    if name in ("embed", "head"):
        return P(None, "fsdp")                    # [V, E]
    return P()  # replicated (norms, biases, router)


def shard_params(params: Dict[str, Any], mesh) -> Dict[str, Any]:
    """Apply rules with jax.device_put (NamedSharding)."""
    import flax
    from jax.sharding import NamedSharding

    flat = flax.traverse_util.flatten_dict(params)
    out = {
        k: jax.device_put(v, NamedSharding(mesh, param_sharding_rules(k)))
        for k, v in flat.items()
    }
    return flax.traverse_util.unflatten_dict(out)


def param_spec_tree(params: Dict[str, Any]):
    import flax

    flat = flax.traverse_util.flatten_dict(params)
    specs = {k: param_sharding_rules(k) for k in flat}
    return flax.traverse_util.unflatten_dict(specs)
