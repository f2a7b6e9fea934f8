"""MNIST HPO trial workload — flax re-design of the reference's
pytorch-mnist trial image (examples/v1beta1/trial-images/pytorch-mnist/
mnist.py: conv-conv-fc net, SGD with lr/momentum hyperparameters, prints
per-epoch loss/accuracy for the collector).

``run_mnist_trial_packed`` is the pack-aware variant (controller/packing.py):
the SAME vectorized code trains a population of K members under ``jax.vmap``
— K > 1 when the scheduler packed compatible trials into one program, K = 1
when a trial runs solo through the normal executor — so packed and
sequential runs execute identical per-member programs."""

from __future__ import annotations

from typing import Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..utils.datasets import batches, load_mnist


class MnistCNN(nn.Module):
    """mnist.py Net: two convs + two dense layers. Widths default to the
    reference image's (20/50/500); smaller widths make the "small
    MNIST-CNN" of the packing tests (tests/test_packing.py)."""

    conv1: int = 20
    conv2: int = 50
    hidden: int = 500

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = nn.Conv(self.conv1, (5, 5))(x)
        x = nn.max_pool(nn.relu(x), (2, 2), strides=(2, 2))
        x = nn.Conv(self.conv2, (5, 5))(x)
        x = nn.max_pool(nn.relu(x), (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(self.hidden)(x))
        return nn.Dense(10)(x)


def run_mnist_trial(assignments: Dict[str, str], ctx=None) -> None:
    """Entry point: hyperparameters lr / momentum (+ optional batch_size,
    num_epochs, num_train_examples); reports loss and accuracy."""
    lr = float(assignments.get("lr", "0.01"))
    momentum = float(assignments.get("momentum", "0.5"))
    batch_size = int(assignments.get("batch_size", "64"))
    num_epochs = int(assignments.get("num_epochs", "1"))
    n_train = int(assignments.get("num_train_examples", "0")) or None

    x, y = load_mnist("train", n=n_train)
    x_test, y_test = load_mnist("test", n=(n_train // 5 if n_train else None))

    model = MnistCNN()
    from ..utils.modelinit import jitted_init

    params = jitted_init(model, jax.random.PRNGKey(0), jnp.zeros((2,) + x.shape[1:]))
    tx = optax.sgd(lr, momentum=momentum)
    opt_state = tx.init(params)

    @jax.jit
    def train_step(params, opt_state, bx, by):
        def loss_fn(p):
            logits = model.apply({"params": p}, bx)
            return optax.softmax_cross_entropy_with_integer_labels(logits, by).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    @jax.jit
    def eval_step(params, bx, by):
        logits = model.apply({"params": params}, bx, train=False)
        return (jnp.argmax(logits, -1) == by).mean()

    from ..utils.prefetch import prefetch_to_device

    rng = np.random.default_rng(0)
    for epoch in range(num_epochs):
        losses = []
        for bx, by in prefetch_to_device(batches(x, y, batch_size, rng)):
            params, opt_state, loss = train_step(params, opt_state, bx, by)
            losses.append(loss)
        accs = [
            eval_step(params, bx, by)
            for bx, by in prefetch_to_device(batches(x_test, y_test, batch_size, rng))
        ]
        if not accs and len(x_test):  # test split smaller than one batch
            accs = [eval_step(params, x_test, y_test)]
        metrics = {
            "loss": float(jnp.stack(losses).mean()) if losses else float("nan"),
            "accuracy": float(jnp.stack(accs).mean()) if accs else 0.0,
        }
        if ctx is not None:
            ctx.report(**metrics)
        else:
            print(f"loss={metrics['loss']}")
            print(f"accuracy={metrics['accuracy']}")


def run_mnist_trial_packed(assignments, ctx=None) -> None:
    """Pack-aware MNIST trial: a population of K (lr, momentum) members
    trains as ONE ``jax.vmap``-ed program over shared batches — the
    podracer/Anakin batched-learner idiom. Shape-affecting knobs
    (batch_size, num_epochs, num_train_examples) must agree across the pack
    (runtime.packed.uniform_param raises otherwise). Runs unchanged in solo
    mode as a K=1 population."""
    from ..runtime.packed import population_of, report_population, uniform_param

    pop = population_of(assignments)
    packed = ctx is not None and hasattr(ctx, "pack_size")
    k = ctx.pack_size if packed else 1

    batch_size = int(uniform_param(pop, "batch_size", 64))
    num_epochs = int(uniform_param(pop, "num_epochs", 1))
    n_train = int(uniform_param(pop, "num_train_examples", 0)) or None

    lr = jnp.asarray(pop.get("lr", np.full((k,), 0.01, np.float32)))
    momentum = jnp.asarray(pop.get("momentum", np.full((k,), 0.5, np.float32)))

    x, y = load_mnist("train", n=n_train)
    x_test, y_test = load_mnist("test", n=(n_train // 5 if n_train else None))

    model = MnistCNN(
        conv1=int(uniform_param(pop, "conv1_channels", 20)),
        conv2=int(uniform_param(pop, "conv2_channels", 50)),
        hidden=int(uniform_param(pop, "hidden_size", 500)),
    )
    from ..utils.modelinit import jitted_init

    # identical init across members — exactly what each solo trial computes
    params0 = jitted_init(model, jax.random.PRNGKey(0), jnp.zeros((2,) + x.shape[1:]))
    params = jax.tree_util.tree_map(lambda p: jnp.stack([p] * k), params0)
    velocity = jax.tree_util.tree_map(jnp.zeros_like, params)

    def member_step(p, v, lr_i, mom_i, bx, by):
        """SGD-with-momentum (optax.sgd trace semantics, hand-rolled so lr
        and momentum vmap as per-member scalars)."""

        def loss_fn(p):
            logits = model.apply({"params": p}, bx)
            return optax.softmax_cross_entropy_with_integer_labels(logits, by).mean()

        loss, grads = jax.value_and_grad(loss_fn)(p)
        v = jax.tree_util.tree_map(lambda g, vv: g + mom_i * vv, grads, v)
        p = jax.tree_util.tree_map(lambda pp, vv: pp - lr_i * vv, p, v)
        return p, v, loss

    def masked_step(p, v, lr_, mom_, active, bx, by):
        """One vmapped population step; frozen (early-stopped/killed) members
        keep their state via jnp.where instead of unwinding the pack."""
        p_new, v_new, loss = jax.vmap(
            member_step, in_axes=(0, 0, 0, 0, None, None)
        )(p, v, lr_, mom_, bx, by)

        def keep(new, old):
            mask = active.reshape((-1,) + (1,) * (new.ndim - 1))
            return jnp.where(mask, new, old)

        return (
            jax.tree_util.tree_map(keep, p_new, p),
            jax.tree_util.tree_map(keep, v_new, v),
            loss,
        )

    train_step = jax.jit(masked_step)

    def member_eval(p, bx, by):
        logits = model.apply({"params": p}, bx, train=False)
        return (jnp.argmax(logits, -1) == by).mean()

    eval_step = jax.jit(jax.vmap(member_eval, in_axes=(0, None, None)))

    def active_mask():
        if packed:
            return jnp.asarray(ctx.active_mask)
        return jnp.ones((k,), dtype=bool)

    rng = np.random.default_rng(0)
    for epoch in range(num_epochs):
        losses = []
        for bx, by in batches(x, y, batch_size, rng):
            params, velocity, loss = train_step(
                params, velocity, lr, momentum, active_mask(),
                jnp.asarray(bx), jnp.asarray(by),
            )
            losses.append(loss)
        accs = [
            eval_step(params, jnp.asarray(bx), jnp.asarray(by))
            for bx, by in batches(x_test, y_test, batch_size, rng)
        ]
        if not accs and len(x_test):
            accs = [eval_step(params, jnp.asarray(x_test), jnp.asarray(y_test))]
        loss_pop = (
            jnp.stack(losses).mean(axis=0)
            if losses
            else jnp.full((k,), float("nan"))
        )
        acc_pop = jnp.stack(accs).mean(axis=0) if accs else jnp.zeros((k,))
        report_population(
            ctx, loss=np.asarray(loss_pop), accuracy=np.asarray(acc_pop)
        )


run_mnist_trial_packed.supports_packing = True


def abstract_mnist_program(assignments: Dict[str, str]):
    """Abstract program probe (katib_tpu.analysis.program): the canonical
    jitted train step of the MNIST trial, described with ShapeDtypeStruct
    avals only — eval_shape for the parameter tree, no arrays, no devices.

    lr/momentum enter as traced f32 scalar inputs (runtime-scalar: one
    executable covers the whole sweep); the model widths and batch_size
    select different avals (shape-affecting: one compile per value);
    num_epochs / num_train_examples are host-side loop knobs."""
    from ..analysis.program import ProgramProbe

    batch_size = int(assignments.get("batch_size", "64"))
    model = MnistCNN(
        conv1=int(assignments.get("conv1_channels", "20")),
        conv2=int(assignments.get("conv2_channels", "50")),
        hidden=int(assignments.get("hidden_size", "500")),
    )
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)  # raw PRNG key, abstract
    probe_x = jax.ShapeDtypeStruct((2, 28, 28, 1), jnp.float32)
    params = jax.eval_shape(
        lambda r, x: model.init(r, x)["params"], rng, probe_x
    )
    bx = jax.ShapeDtypeStruct((batch_size, 28, 28, 1), jnp.float32)
    by = jax.ShapeDtypeStruct((batch_size,), jnp.int32)
    lr = jax.ShapeDtypeStruct((), jnp.float32)
    momentum = jax.ShapeDtypeStruct((), jnp.float32)

    def train_step(params, velocity, lr, momentum, bx, by):
        # SGD-with-momentum with lr/momentum as traced per-call scalars —
        # the same member program run_mnist_trial_packed vmaps (and the
        # shape-bucketed program a shared-executable sweep would compile)
        def loss_fn(p):
            logits = model.apply({"params": p}, bx)
            return optax.softmax_cross_entropy_with_integer_labels(logits, by).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        velocity = jax.tree_util.tree_map(lambda g, v: g + momentum * v, grads, velocity)
        params = jax.tree_util.tree_map(lambda p, v: p - lr * v, params, velocity)
        return params, velocity, loss

    return ProgramProbe(
        fn=train_step,
        args=(params, params, lr, momentum, bx, by),
        params=params,
        hyperparams={"lr": lr, "momentum": momentum},
        host_params={"num_epochs", "num_train_examples"},
    )


run_mnist_trial.abstract_program = abstract_mnist_program
run_mnist_trial_packed.abstract_program = abstract_mnist_program
