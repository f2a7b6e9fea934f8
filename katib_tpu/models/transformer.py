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
import math
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.flash_attention import flash_attention, sharded_flash_attention
from ..ops.ring_attention import dense_attention, ring_attention


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

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.num_heads


def bench_lm_config(size: str, on_tpu: bool):
    """The canonical benchmark LM shapes — single source for bench.py,
    scripts/tune_tpu.py and scripts/profile_lm.py so a retune can't leave
    one of them measuring a stale configuration. Returns
    ``(config_kwargs, batch, seq, effective_size)``; off-TPU every size
    degrades to the sub-minute CPU smoke shape (and says so in
    ``effective_size``)."""
    if not on_tpu:
        return (
            dict(vocab_size=512, embed_dim=128, num_layers=2, num_heads=4,
                 max_seq_len=256, dtype=jnp.float32),
            4, 256, "cpu_smoke",
        )
    if size == "large":
        return (
            dict(vocab_size=32768, embed_dim=1024, num_layers=8, num_heads=16,
                 max_seq_len=2048, dtype=jnp.bfloat16),
            4, 2048, "large",
        )
    return (
        dict(vocab_size=8192, embed_dim=512, num_layers=4, num_heads=8,
             max_seq_len=1024, dtype=jnp.bfloat16),
        8, 1024, "small",
    )


def rotary_embed(x: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
    """RoPE on [B, T, H, D] with explicit global positions [B, T]."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32) * (math.log(10000.0) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, T, half]
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


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


class Attention(nn.Module):
    config: TransformerConfig
    mesh: Optional[Any] = None
    # Set when this module is traced INSIDE a shard_map that is manual over
    # a sequence axis (pipeline stages with sequence parallelism): attention
    # runs the ring schedule directly over that axis instead of wrapping its
    # own shard_map. positions must be GLOBAL (caller offsets by rank).
    seq_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        h, d = cfg.num_heads, cfg.head_dim
        q, k, v = QKVProjection(h, d, dtype=cfg.dtype, name="qkv")(x)
        q = rotary_embed(q, positions)
        k = rotary_embed(k, positions)
        if self.seq_axis is not None:
            from ..ops.ring_attention import ring_attention_local

            o = ring_attention_local(q, k, v, self.seq_axis, causal=cfg.causal)
        elif self.mesh is not None:
            from ..parallel.mesh import mesh_axis_sizes

            sizes = mesh_axis_sizes(self.mesh)
            # 'expert' is a batch axis here: outside the MoE layers it acts
            # as pure data parallelism (see parallel.mesh.activation_batch_axes)
            batch_axes = ("data", "fsdp", "expert")
            if sizes.get("seq", 1) > 1:
                # cross-device sequence blocks: ring schedule over ppermute
                o = ring_attention(
                    q, k, v, self.mesh, causal=cfg.causal, batch_axes=batch_axes
                )
            else:
                # seq unsharded: fused Pallas flash kernel per local shard
                o = sharded_flash_attention(
                    q, k, v, self.mesh, causal=cfg.causal, batch_axes=batch_axes
                )
        else:
            o = flash_attention(q, k, v, causal=cfg.causal)
        return nn.DenseGeneral(
            cfg.embed_dim, axis=(-2, -1), use_bias=False, dtype=cfg.dtype, name="out"
        )(o)


class MLP(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        hidden = cfg.embed_dim * cfg.mlp_ratio
        up = nn.Dense(hidden, use_bias=False, dtype=cfg.dtype, name="up")(x)
        gate = nn.Dense(hidden, use_bias=False, dtype=cfg.dtype, name="gate")(x)
        return nn.Dense(cfg.embed_dim, use_bias=False, dtype=cfg.dtype, name="down")(
            nn.silu(gate) * up
        )


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

    @nn.compact
    def __call__(self, x, positions):
        x = _pin_residual(
            x + Attention(self.config, self.mesh, self.seq_axis, name="attn")(
                RMSNorm(name="ln1")(x), positions
            ),
            self.mesh,
        )
        if self.config.num_experts > 0:
            x = x + MoE(
                self.config, self.mesh, self.expert_axis, self.expert_axis_size,
                name="moe",
            )(RMSNorm(name="ln2")(x))
        else:
            x = x + MLP(self.config, name="mlp")(RMSNorm(name="ln2")(x))
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
            x = Block(cfg, self.mesh, name=f"block{i}")(x, positions)
        x = RMSNorm(name="ln_f")(x)
        # tied output head — the largest matmul in the model: bf16 operands
        # at native MXU rate, f32 accumulation for the softmax/loss
        logits = jnp.einsum(
            "bte,ve->btv", x.astype(cfg.dtype), emb.astype(cfg.dtype),
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
    if name == "embed":
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
