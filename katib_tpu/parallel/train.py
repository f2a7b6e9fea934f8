"""Distributed training-step builder: pjit over a named mesh with full
dp/fsdp/tp/sp shardings.

The TPU-native counterpart of the reference's delegated distributed trials
(PyTorchJob-DDP / MPIJob-Horovod, SURVEY.md §2.9): one jitted step where XLA
inserts every collective — gradient psum/reduce-scatter over 'data'/'fsdp',
activation all-gathers for TP ('model'), ring collective-permutes for
sequence parallelism ('seq').
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import optax

from ..models.transformer import (
    TransformerConfig,
    TransformerLM,
    param_sharding_rules,
)
from ..tracing import enter_annotation, leave_annotation, step_part


def lm_loss(logits: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    return optax.softmax_cross_entropy_with_integer_labels(logits, targets).mean()


def _default_device():
    """First device of the initialized backend, through the one probe
    (utils/backend.py — KTI304): inside a train-step builder the backend is
    normally already up, so this is one cached-verdict check and a direct
    call; with no backend it raises."""
    from ..utils.backend import require_devices

    return require_devices()[0]


def make_lm_train_step(
    config: TransformerConfig,
    mesh,
    learning_rate: float = 1e-3,
    seed: int = 0,
):
    """Returns (params, opt_state, step_fn, positions_fn).

    step_fn(params, opt_state, tokens, targets) -> (params, opt_state, loss),
    jitted with NamedShardings: tokens/targets P(('data','fsdp'), 'seq'),
    params per katib_tpu.models.transformer.param_sharding_rules. A model with
    "routed" layers returns a fourth value, its routing counters summed over
    those layers (models.transformer.collect_routing): device scalars that are
    ready when the loss is. Where the routers carry a selection bias, AdamW
    leaves it alone and the step moves it by its rule
    (models.transformer.move_router_bias), and ``bias_span`` is among the
    counters; a sequence-wise balance loss is part of ``loss``.
    """
    import flax
    from jax.sharding import NamedSharding, PartitionSpec as P

    # Single-device mesh: GSPMD partitioning buys nothing on one chip, so
    # build the plain jit step instead of one over a 1-device NamedSharding
    # — semantics are identical, collectives are no-ops on one device.
    single_device = mesh is None or int(mesh.devices.size) == 1
    target_device = None if mesh is None else mesh.devices.reshape(-1)[0]
    multiprocess = not single_device and jax.process_count() > 1

    model = TransformerLM(config, mesh=None if single_device else mesh)
    sample_tokens = jnp.zeros((2, 16), dtype=jnp.int32)
    from ..utils.modelinit import jitted_init

    if not multiprocess:
        # (multi-process ranks can't pin another process's device — and their
        # params are created globally sharded below, not materialized here)
        params = jitted_init(
            model, jax.random.PRNGKey(seed), sample_tokens,
            device=target_device if single_device else _default_device(),
        )

    routed = any(config.layer(i).mlp == "routed" for i in range(config.num_layers))
    # the routers' selection bias moves by its own rule after the step, and AdamW leaves it alone
    biased = routed and config.routed.bias_rate > 0
    adjusted = routed and (biased or config.routed.balance_weight > 0)
    tx = optax.adamw(learning_rate, weight_decay=0.01)
    if biased:
        tx = optax.masked(tx, lambda tree: flax.traverse_util.path_aware_map(
            lambda path, _: path[-2:] != ("experts", "bias"), tree))

    if single_device:
        # Params and batches stay UNCOMMITTED (no device_put). Placement on
        # a non-default chip (a trial gang-allocated to chip k of a
        # multi-chip host) comes from running creation and every step under
        # jax.default_device(target) instead of committing.
        batch_mesh = None
    elif multiprocess:
        # Multi-host gang (MultiHostExecutor workers): params must be born
        # globally sharded — device_put can't target another process's
        # devices. jit with out_shardings materializes each process's
        # addressable shards directly from one traced init.
        shapes = jax.eval_shape(
            lambda k: model.init(k, sample_tokens)["params"], jax.random.PRNGKey(seed)
        )
        flat_specs = {
            k: NamedSharding(mesh, param_sharding_rules(k))
            for k in flax.traverse_util.flatten_dict(shapes)
        }
        sharding_tree = flax.traverse_util.unflatten_dict(flat_specs)
        init_fn = jax.jit(
            lambda k: model.init(k, sample_tokens)["params"],
            out_shardings=sharding_tree,
        )
        params = init_fn(jax.random.PRNGKey(seed))
        batch_mesh = mesh
    else:
        # shard params + opt state
        flat_specs = {
            k: param_sharding_rules(k)
            for k in flax.traverse_util.flatten_dict(params)
        }
        param_specs = flax.traverse_util.unflatten_dict(flat_specs)
        params = jax.tree.map(
            lambda v, s: jax.device_put(v, NamedSharding(mesh, s)),
            params,
            param_specs,
            is_leaf=lambda x: not isinstance(x, dict),
        )
        batch_mesh = mesh

    # Non-default target chip: uncommitted execution follows the *default*
    # device, so pin creation and every step with jax.default_device.
    pin_device = (
        target_device
        if single_device
        and target_device is not None
        and target_device != _default_device()
        else None
    )

    if pin_device is None:
        opt_state = tx.init(params)
    else:
        with jax.default_device(pin_device):
            opt_state = tx.init(params)

    def step(params, opt_state, tokens, targets, positions):
        def loss_fn(p):
            if config.num_experts > 0:
                from ..models.transformer import collect_moe_aux

                logits, mutated = model.apply(
                    {"params": p}, tokens, positions, mutable=["intermediates"]
                )
                with step_part("head_loss"):
                    return lm_loss(logits, targets) + collect_moe_aux(mutated), ()
            if routed:
                from ..models.transformer import collect_router_state, collect_routing

                logits, mutated = model.apply(
                    {"params": p}, tokens, positions, mutable=["intermediates"]
                )
                if not adjusted:  # nothing is added to the loss for these layers
                    with step_part("head_loss"):
                        loss = lm_loss(logits, targets)
                    with step_part("router"):
                        return loss, (collect_routing(mutated),)
                # a balance loss is added, the expert loads are kept for the bias
                with step_part("router"):
                    balance, loads = collect_router_state(mutated)
                with step_part("head_loss"):
                    loss = lm_loss(logits, targets) + balance
                with step_part("router"):
                    return loss, (collect_routing(mutated), loads)
            logits = model.apply({"params": p}, tokens, positions)
            with step_part("head_loss"):
                return lm_loss(logits, targets), ()

        (loss, counters), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        with step_part("optimizer"):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            if adjusted:
                from ..models.transformer import move_router_bias

                routing, loads = counters
                params, span = move_router_bias(params, loads, config.routed.bias_rate)
                counters = (dict(routing, bias_span=span) if biased else routing,)
        return (params, opt_state, loss) + counters

    jitted_step = jax.jit(step, donate_argnums=(0, 1))

    if pin_device is None:
        step_fn = jitted_step
    else:
        def step_fn(*args):
            with jax.default_device(pin_device):
                return jitted_step(*args)

    def put_batch(tokens, targets, positions=None):
        import contextlib
        import numpy as np

        if positions is None:
            b, t = tokens.shape
            positions = np.broadcast_to(np.arange(t, dtype="int32"), (b, t))
        if batch_mesh is None:
            ctx = (
                jax.default_device(pin_device)
                if pin_device is not None
                else contextlib.nullcontext()
            )
            with ctx:
                return jnp.asarray(tokens), jnp.asarray(targets), jnp.asarray(positions)
        from ..parallel.mesh import batch_spec

        batch_sharding = NamedSharding(
            batch_mesh, batch_spec(tokens.shape[0], batch_mesh)
        )
        return (
            jax.device_put(tokens, batch_sharding),
            jax.device_put(targets, batch_sharding),
            jax.device_put(positions, batch_sharding),
        )

    return params, opt_state, step_fn, put_batch


def run_lm_trial(assignments: Dict[str, str], ctx=None) -> None:
    """HPO trial over the distributed LM: hyperparameters learning_rate,
    embed_dim, num_layers; reports per-epoch loss. Builds its mesh from the
    trial's gang-allocated devices (dp [+ tp/sp via assignments]).

    The model is the dense decoder of four sizes (vocab_size, embed_dim,
    num_layers, num_heads), or — with an ``architecture`` assignment, the path
    of a JSON file of published config keys — the decoder that file describes,
    whole (models/architecture.py, docs/architecture-handoff.md); the four
    sizes may then not be given too."""
    import numpy as np

    from .mesh import make_mesh

    lr = float(assignments.get("learning_rate", "1e-3"))
    tp = int(assignments.get("tensor_parallel", "1"))
    sp = int(assignments.get("sequence_parallel", "1"))
    steps = int(assignments.get("num_steps", "20"))
    batch = int(assignments.get("batch_size", "8"))
    seq_len = int(assignments.get("seq_len", "128"))

    devices = ctx.jax_devices() or None if ctx is not None else None
    mesh = make_mesh(devices, model=tp, seq=sp)

    if "architecture" in assignments:
        from ..models.architecture import architecture_config, load_architecture

        sizes = sorted({"vocab_size", "embed_dim", "num_layers", "num_heads"} & set(assignments))
        if sizes:
            raise ValueError(f"an architecture is handed in whole: {sizes} may not be given beside it")
        config = architecture_config(load_architecture(assignments["architecture"]), seq_len)
    else:
        config = TransformerConfig(
            vocab_size=int(assignments.get("vocab_size", "512")),
            embed_dim=int(assignments.get("embed_dim", "128")),
            num_layers=int(assignments.get("num_layers", "2")),
            num_heads=int(assignments.get("num_heads", "4")),
            max_seq_len=seq_len,
        )
    vocab = config.vocab_size
    import contextlib

    # stages of the `compile` span (runtime/context.py); the step's own trace,
    # lowering and compile become its children through jax.monitoring
    stage = ctx.span if ctx is not None else (lambda name: contextlib.nullcontext())
    with stage("build"):
        params, opt_state, step_fn, put_batch = make_lm_train_step(config, mesh, lr)
    if ctx is not None:
        step_fn = ctx.watch_step(step_fn)

    rng = np.random.default_rng(0)
    data = rng.integers(0, vocab, size=(batch, seq_len + 1), dtype=np.int32)
    profile = ctx is not None and assignments.get("profile", "0") == "1"

    prof_cm = ctx.profile() if profile else contextlib.nullcontext()
    # the synthetic batch is constant across steps: stage it once
    with stage("stage_batch"):
        tokens, targets, positions = put_batch(data[:, :-1], data[:, 1:])
    traced = getattr(ctx, "tracer", None) is not None

    def report(loss, counters):
        # a profiler annotation only: the device idles from this step's end to the next dispatch
        note = enter_annotation("fetch") if traced else None
        try:
            value = float(loss)  # the one wait for the device
            fetched = [{name: float(v) for name, v in routing.items()} for routing in counters]
        finally:
            leave_annotation(note)
        for routing in fetched:  # of the step whose loss this is: ready with it
            ctx.count(**routing)
        ctx.report(loss=value)

    with prof_cm:
        for i in range(steps):
            params, opt_state, loss, *counters = step_fn(
                params, opt_state, tokens, targets, positions)
            if ctx is not None and (i + 1) % 5 == 0:
                report(loss, counters)
    if ctx is not None:
        if steps % 5 != 0:  # final value not yet reported by the loop
            report(loss, counters)
    else:
        print(f"loss={float(loss)}")


# semantic-analysis probe (katib_tpu.analysis.program): the abstract twin of
# this trial's train step lives next to the model it shapes
from ..models.transformer import abstract_lm_program  # noqa: E402

run_lm_trial.abstract_program = abstract_lm_program
