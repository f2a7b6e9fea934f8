"""REAL-data HPO record: Bayesian optimization on the UCI handwritten digits.

The round-4 review's top finding (Missing #1) was that every accuracy claim
rested on a synthetic stand-in, leaving the real-dataset axis of
BASELINE.json unverified — CIFAR-10/MNIST downloads are blocked by zero
egress. sklearn's bundled ``load_digits`` (1797 genuine 8x8 scans of
handwritten digits) is real data that ships with the environment, so this
script closes the axis at the scale that is actually possible here:

- a controller-driven experiment through the FULL stack (suggestion
  protocol, scheduler, collectors, status, persistence);
- ``bayesianoptimization`` with its reference-default ``gp_hedge``
  acquisition portfolio (the round-5 implementation), searching lr x width
  x weight-decay of a small CNN;
- a genuine held-out split (360 real images never seen in training);
- the e2e verifier as the pass gate, accuracy quartiles + per-trial table
  recorded to ``examples/records/digits_hpo_<platform>.json``.

Reference counterpart: the hp-tuning examples the reference CI runs on real
MNIST (examples/v1beta1/hp-tuning/bayesian-optimization.yaml with
pytorch-mnist trial images).

Usage: python scripts/run_digits_hpo.py [--tpu] [--trials N] [--timeout S]
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


# Single source for the scale the record's provenance block reports —
# the trial, the spec default, and the captured artifact must agree.
IMAGE_SIZE = 16
EPOCHS = 8


def digits_trial(assignments, ctx):
    """Width-parameterized CNN on real digits; reports held-out accuracy
    per epoch so early-stopping/collector paths see a metric series."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    import flax.linen as nn

    from katib_tpu.utils.datasets import batches, load_digits

    lr = float(assignments["lr"])
    width = int(float(assignments["width"]))
    weight_decay = float(assignments["weight_decay"])
    epochs = int(float(assignments.get("epochs", str(EPOCHS))))

    # 16x16 keeps two pool stages meaningful; grayscale 1-channel stem
    xtr, ytr = load_digits("train", image_size=IMAGE_SIZE)
    xv, yv = load_digits("test", image_size=IMAGE_SIZE)

    class CNN(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.relu(nn.Conv(width, (3, 3))(x))
            x = nn.max_pool(x, (2, 2), strides=(2, 2))
            x = nn.relu(nn.Conv(2 * width, (3, 3))(x))
            x = nn.max_pool(x, (2, 2), strides=(2, 2))
            x = x.mean(axis=(1, 2))
            return nn.Dense(10)(x)

    m = CNN()
    p = m.init(jax.random.PRNGKey(0), xtr[:2])
    tx = optax.adamw(lr, weight_decay=weight_decay)
    st = tx.init(p)

    @jax.jit
    def step(p, st, xb, yb):
        def loss(p):
            lg = m.apply(p, xb)
            return optax.softmax_cross_entropy_with_integer_labels(lg, yb).mean()

        g = jax.grad(loss)(p)
        up, st2 = tx.update(g, st, p)
        return optax.apply_updates(p, up), st2

    @jax.jit
    def evaluate(p, xv, yv):
        pred = jnp.argmax(m.apply(p, xv), -1)
        return (pred == yv).mean()

    rng = np.random.default_rng(0)
    xvj, yvj = jnp.asarray(xv), jnp.asarray(yv)
    for _ in range(epochs):
        for xb, yb in batches(xtr, ytr, 64, rng):
            p, st = step(p, st, jnp.asarray(xb), jnp.asarray(yb))
        acc = float(evaluate(p, xvj, yvj))
        ctx.report(**{"Validation-accuracy": acc})


def build_spec(name, trials, parallel, epochs=EPOCHS):
    from katib_tpu.api import (
        AlgorithmSpec, Distribution, ExperimentSpec, FeasibleSpace,
        ObjectiveSpec, ObjectiveType, ParameterSpec, ParameterType,
        TrialTemplate,
    )

    def trial_fn(assignments, ctx):
        digits_trial({**assignments, "epochs": str(epochs)}, ctx)

    return ExperimentSpec(
        name=name,
        objective=ObjectiveSpec(
            type=ObjectiveType.MAXIMIZE,
            objective_metric_name="Validation-accuracy",
        ),
        # no explicit acq setting: exercises the reference-default gp_hedge
        algorithm=AlgorithmSpec("bayesianoptimization"),
        parameters=[
            ParameterSpec("lr", ParameterType.DOUBLE,
                          FeasibleSpace(min="0.00003", max="0.1",
                                        distribution=Distribution.LOG_UNIFORM)),
            ParameterSpec("width", ParameterType.INT,
                          FeasibleSpace(min="4", max="24")),
            ParameterSpec("weight_decay", ParameterType.DOUBLE,
                          FeasibleSpace(min="0.0000001", max="0.01",
                                        distribution=Distribution.LOG_UNIFORM)),
        ],
        trial_template=TrialTemplate(function=trial_fn),
        max_trial_count=trials,
        parallel_trial_count=parallel,
    )


def main() -> None:
    import statistics

    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=25)
    ap.add_argument("--timeout", type=float, default=1500.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--tpu", action="store_true",
                    help="run on the accelerator backend (default forces CPU)")
    args = ap.parse_args()

    if not args.tpu:
        os.environ["JAX_PLATFORMS"] = "cpu"  # before anything imports jax
    import jax

    if not args.tpu:
        jax.config.update("jax_platforms", "cpu")
    from katib_tpu.utils.compilation import enable_compilation_cache

    enable_compilation_cache()
    platform = jax.devices()[0].platform

    from katib_tpu.controller.experiment import ExperimentController
    from katib_tpu.utils.datasets import DIGITS_PROVENANCE, load_digits
    from run_capability_records import _record

    n_train = len(load_digits("train")[1])
    n_val = len(load_digits("test")[1])
    name = "digits-hpo-real"
    root = tempfile.mkdtemp(prefix="digits-hpo-")
    ctrl = ExperimentController(root_dir=root)
    try:
        ctrl.create_experiment(build_spec(name, args.trials, parallel=1))
        t0 = time.time()
        exp = ctrl.run(name, timeout=args.timeout)
        rec = _record(ctrl, exp, name, "bayesianoptimization:gp_hedge",
                      time.time() - t0, {
            "dataset": DIGITS_PROVENANCE,
            "dataset_is_real": True,
            "scale": {"image_size": IMAGE_SIZE, "n_train": n_train,
                      "n_val": n_val, "epochs_per_trial": EPOCHS},
            "reference": "examples/v1beta1/hp-tuning/bayesian-optimization.yaml",
        })
        rec["platform"] = platform
        rec["device_kind"] = getattr(jax.devices()[0], "device_kind", platform)
        out = args.out or os.path.join(
            REPO, "examples", "records", f"digits_hpo_{platform}.json")
        if os.path.dirname(out):
            os.makedirs(os.path.dirname(out), exist_ok=True)
        with open(out, "w") as f:
            json.dump(rec, f, indent=1)
        brief = {k: v for k, v in rec.items() if k != "trials"}
        print(json.dumps(brief, indent=1))
        print(f"record written to {out}", flush=True)
        accs = [t["val_acc"] for t in rec["trials"] if t["val_acc"] is not None]
        ok = rec["verification"] == "ok" and len(accs) == args.trials
        if accs:
            print(f"real-data spread: min={min(accs):.3f} "
                  f"median={statistics.median(accs):.3f} max={max(accs):.3f}",
                  flush=True)
        raise SystemExit(0 if ok else 1)
    finally:
        ctrl.close()
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    main()
