"""Capture ENAS and Hyperband experiment records on the accelerator.

Round-4 review: the records directory was DARTS-only, while the reference's
CI exercises ENAS (e2e-test-enas-cifar10.yaml) and hyperband
(examples/v1beta1/hp-tuning/hyperband.yaml) as first-class capabilities.
This script runs both through the FULL framework stack (REINFORCE
suggestion loop / bracket protocol, scheduler, collectors, status) at a
scale where the round-5 calibrated objective discriminates, verifies the
reference e2e invariants, and writes
``examples/records/{enas,hyperband}_<platform>.json``.

Usage: python scripts/run_capability_records.py [--tpu]
           [--which enas|hyperband|both] [--timeout S]
"""

import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))  # run_north_star


def _acc_stats(ctrl, name):
    accs, per_trial = [], []
    for t in ctrl.state.list_trials(name):
        m = t.observation.metric("Validation-accuracy") if t.observation else None
        acc = float(m.max) if m is not None and m.max != "unavailable" else None
        if acc is not None:
            accs.append(acc)
        per_trial.append({
            "name": t.name,
            "condition": t.condition.value,
            "val_acc": acc,
            "assignments": t.assignments_dict(),
        })
    return accs, per_trial


def _record(ctrl, exp, name, algorithm, wallclock, extra):
    from katib_tpu.utils.e2e_verify import verify_experiment_results

    verification = "ok"
    try:
        verify_experiment_results(ctrl, exp)
    except Exception as e:
        verification = f"verification failed: {type(e).__name__}: {e}"
    accs, per_trial = _acc_stats(ctrl, name)
    opt = exp.status.current_optimal_trial
    rec = {
        "experiment": name,
        "algorithm": algorithm,
        "n_trials": len(per_trial),
        "n_succeeded": exp.status.trials_succeeded,
        "wallclock_s": round(wallclock, 1),
        "best_val_acc": max(accs) if accs else None,
        "median_val_acc": round(statistics.median(accs), 4) if accs else None,
        "acc_quartiles": [round(q, 4) for q in statistics.quantiles(accs, n=4)]
        if len(accs) >= 4 else None,
        "optimal_assignments": {a.name: a.value for a in opt.parameter_assignments}
        if opt else None,
        "reason": exp.status.reason.value,
        "verification": verification,
        "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "trials": per_trial,
    }
    rec.update(extra)
    return rec


def _cnn_trainer(lr, steps, xtr, ytr, xv, yv):
    """Small fixed CNN on the calibrated stand-in — accuracy tracks lr and
    step budget, which is exactly what hyperband's resource halving and the
    record's non-degenerate-objective requirement need."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    import flax.linen as nn

    from katib_tpu.utils.datasets import batches

    class CNN(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.relu(nn.Conv(12, (3, 3))(x))
            x = nn.max_pool(x, (2, 2), strides=(2, 2))
            x = nn.relu(nn.Conv(24, (3, 3))(x))
            x = nn.max_pool(x, (2, 2), strides=(2, 2))
            x = nn.relu(nn.Conv(24, (3, 3))(x))
            x = x.mean(axis=(1, 2))
            return nn.Dense(10)(x)

    m = CNN()
    p = m.init(jax.random.PRNGKey(0), xtr[:2])
    tx = optax.adam(lr)
    st = tx.init(p)

    @jax.jit
    def step(p, st, xb, yb):
        def loss(p):
            lg = m.apply(p, xb)
            return optax.softmax_cross_entropy_with_integer_labels(lg, yb).mean()

        g = jax.grad(loss)(p)
        up, st2 = tx.update(g, st)
        return optax.apply_updates(p, up), st2

    rng = np.random.default_rng(0)
    i = 0
    while i < steps:
        for xb, yb in batches(xtr, ytr, 64, rng):
            p, st = step(p, st, jnp.asarray(xb), jnp.asarray(yb))
            i += 1
            if i >= steps:
                break
    pred = jnp.argmax(m.apply(p, jnp.asarray(xv)), -1)
    import numpy as _np

    return float((_np.asarray(pred) == yv).mean())


def run_enas(ctrl, timeout, scale, dataset="cifar"):
    """REINFORCE controller loop over a layer-wise op search space —
    reference e2e-test-enas-cifar10 equivalent at in-repo scale."""
    from katib_tpu.api import (
        AlgorithmSetting, AlgorithmSpec, ExperimentSpec, FeasibleSpace,
        GraphConfig, NasConfig, NasOperation, ObjectiveSpec, ObjectiveType,
        ParameterSpec, ParameterType, TrialTemplate,
    )

    def enas_trial(assignments, ctx):
        from katib_tpu.models.enas_child import run_enas_trial

        overrides = {
            "num_epochs": str(scale["epochs"]),
            "num_train_examples": str(scale["n_train"]),
            "batch_size": "64",
        }
        if dataset == "digits":
            overrides["dataset"] = "digits"
        run_enas_trial({**assignments, **overrides}, ctx)

    name = "enas-record"
    spec = ExperimentSpec(
        name=name,
        objective=ObjectiveSpec(
            type=ObjectiveType.MAXIMIZE, objective_metric_name="Validation-accuracy"
        ),
        algorithm=AlgorithmSpec(
            "enas", algorithm_settings=[AlgorithmSetting("controller_train_steps", "3")]
        ),
        nas_config=NasConfig(
            graph_config=GraphConfig(
                num_layers=3, input_sizes=[32, 32, 3], output_sizes=[10]
            ),
            operations=[
                NasOperation("convolution", [
                    ParameterSpec("filter_size", ParameterType.CATEGORICAL,
                                  FeasibleSpace(list=["3", "5"])),
                    ParameterSpec("num_filter", ParameterType.CATEGORICAL,
                                  FeasibleSpace(list=["16", "32"])),
                ]),
                NasOperation("separable_convolution", [
                    ParameterSpec("filter_size", ParameterType.CATEGORICAL,
                                  FeasibleSpace(list=["3"])),
                    ParameterSpec("num_filter", ParameterType.CATEGORICAL,
                                  FeasibleSpace(list=["16", "32"])),
                ]),
                NasOperation("reduction", [
                    ParameterSpec("reduction_type", ParameterType.CATEGORICAL,
                                  FeasibleSpace(list=["max_pooling", "avg_pooling"])),
                ]),
            ],
        ),
        trial_template=TrialTemplate(function=enas_trial),
        max_trial_count=scale["trials"],
        parallel_trial_count=1,
    )
    ctrl.create_experiment(spec)
    t0 = time.time()
    exp = ctrl.run(name, timeout=timeout)
    return _record(ctrl, exp, name, "enas", time.time() - t0, {
        "scale": scale,
        "reference": ".github/workflows/e2e-test-enas-cifar10.yaml",
    })


def run_hyperband(ctrl, timeout, scale, dataset="cifar"):
    """Bracket experiment — reference hyperband.yaml shape (lr searched,
    epochs as the halving resource)."""
    from katib_tpu.api import (
        AlgorithmSetting, AlgorithmSpec, Distribution, ExperimentSpec,
        FeasibleSpace, ObjectiveSpec, ObjectiveType, ParameterSpec,
        ParameterType, TrialTemplate,
    )
    from katib_tpu.utils.datasets import load_dataset

    x, y = load_dataset(dataset, "train", n=scale["n_train"])
    n = len(x)  # digits caps at its real 1437-sample split
    split = (3 * n) // 4
    xtr, ytr, xv, yv = x[:split], y[:split], x[split:], y[split:]
    steps_per_epoch = max(split // 64, 1)

    def hb_trial(assignments, ctx):
        lr = float(assignments["lr"])
        epochs = int(float(assignments["epochs"]))
        acc = _cnn_trainer(lr, epochs * steps_per_epoch, xtr, ytr, xv, yv)
        ctx.report(**{"Validation-accuracy": acc})

    name = "hyperband-record"
    spec = ExperimentSpec(
        name=name,
        objective=ObjectiveSpec(
            type=ObjectiveType.MAXIMIZE, objective_metric_name="Validation-accuracy"
        ),
        algorithm=AlgorithmSpec("hyperband", algorithm_settings=[
            AlgorithmSetting("eta", "3"),
            AlgorithmSetting("r_l", "9"),
            AlgorithmSetting("resource_name", "epochs"),
        ]),
        parameters=[
            ParameterSpec("lr", ParameterType.DOUBLE,
                          FeasibleSpace(min="0.0001", max="0.03",
                                        distribution=Distribution.LOG_UNIFORM)),
            ParameterSpec("epochs", ParameterType.INT,
                          FeasibleSpace(min="1", max="9")),
        ],
        trial_template=TrialTemplate(function=hb_trial),
        max_trial_count=60,
        parallel_trial_count=9,
    )
    ctrl.create_experiment(spec)
    t0 = time.time()
    exp = ctrl.run(name, timeout=timeout)
    return _record(ctrl, exp, name, "hyperband", time.time() - t0, {
        "scale": dict(scale, steps_per_epoch=steps_per_epoch),
        "reference": "examples/v1beta1/hp-tuning/hyperband.yaml",
    })


def run_pbt(ctrl, timeout, scale, dataset="cifar"):
    """Population Based Training through the full stack — reference
    simple-pbt example shape (examples/v1beta1/hp-tuning/simple-pbt.yaml /
    trial-images/simple-pbt): a population whose score can only be
    maximized by adapting lr across generations via exploit/explore with
    checkpoint lineage. `dataset` is ignored — the workload is the
    triangle-wave benchmark, which measures the PBT protocol itself
    (generation labels, truncation, checkpoint inheritance), not image
    accuracy."""
    from katib_tpu.api import (
        AlgorithmSetting, AlgorithmSpec, ExperimentSpec, FeasibleSpace,
        ObjectiveSpec, ObjectiveType, ParameterSpec, ParameterType,
        TrialTemplate,
    )
    from katib_tpu.models.simple_pbt import run_pbt_trial

    name = "pbt-record"
    n_pop = 5
    spec = ExperimentSpec(
        name=name,
        objective=ObjectiveSpec(
            type=ObjectiveType.MAXIMIZE, objective_metric_name="Validation-accuracy"
        ),
        algorithm=AlgorithmSpec("pbt", algorithm_settings=[
            AlgorithmSetting("n_population", str(n_pop)),
            AlgorithmSetting("truncation_threshold", "0.4"),
        ]),
        parameters=[
            ParameterSpec("lr", ParameterType.DOUBLE,
                          FeasibleSpace(min="0.0001", max="0.02", step="0.0001")),
        ],
        trial_template=TrialTemplate(function=run_pbt_trial),
        max_trial_count=scale["pbt_trials"],
        parallel_trial_count=n_pop,
    )
    ctrl.create_experiment(spec)
    t0 = time.time()
    exp = ctrl.run(name, timeout=timeout)
    rec = _record(ctrl, exp, name, "pbt", time.time() - t0, {
        "scale": {"n_population": n_pop, "trials": scale["pbt_trials"]},
        "reference": "examples/v1beta1/hp-tuning/simple-pbt.yaml",
    })
    # PBT-specific protocol evidence: generations actually advanced and
    # the final population's scores benefited from checkpoint inheritance
    # (score accumulates across generations in the triangle-wave workload,
    # so max >> a single 20-step round's ceiling of ~0.2 proves lineage).
    from katib_tpu.controller.scheduler import TrialScheduler
    from katib_tpu.suggest.pbt import GENERATION_LABEL

    gens = set()
    lineage = 0
    for t in ctrl.state.list_trials(name):
        g = t.labels.get(GENERATION_LABEL)
        if g is not None:
            gens.add(int(g))
        if TrialScheduler.LINEAGE_LABEL in t.labels:
            lineage += 1
    rec["pbt_generations"] = sorted(gens)
    rec["pbt_lineage_trials"] = lineage
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--which", choices=["enas", "hyperband", "pbt", "all", "both"],
                    default="both",
                    help="'both' = enas+hyperband (watcher compatibility); "
                    "'all' adds pbt")
    ap.add_argument("--timeout", type=float, default=1500.0)
    ap.add_argument("--tpu", action="store_true",
                    help="run on the accelerator backend (default forces CPU)")
    ap.add_argument("--dataset", choices=["cifar", "digits"], default="cifar",
                    help="'digits' runs on the REAL bundled UCI handwritten "
                    "digits (sklearn) instead of the CIFAR loader's "
                    "synthetic stand-in")
    args = ap.parse_args()

    if not args.tpu:
        os.environ["JAX_PLATFORMS"] = "cpu"  # before anything imports jax
    import jax

    if not args.tpu:
        jax.config.update("jax_platforms", "cpu")
    from katib_tpu.utils.compilation import enable_compilation_cache

    enable_compilation_cache()
    platform = jax.devices()[0].platform
    on_tpu = platform != "cpu"
    if on_tpu:
        scale = dict(trials=12, epochs=3, n_train=4096, pbt_trials=40)
    else:  # 1-core box: keep each child to seconds
        scale = dict(trials=4, epochs=1, n_train=512, pbt_trials=25)
    if args.dataset == "digits":
        # clamp to the real split size so the record's provenance reports
        # the training data actually used, not the requested cap
        from katib_tpu.utils.datasets import load_digits

        scale["n_train"] = min(scale["n_train"], len(load_digits("train")[1]))

    from katib_tpu.controller.experiment import ExperimentController

    os.makedirs(os.path.join(REPO, "examples", "records"), exist_ok=True)
    rc = 0
    for which, runner in (
        ("enas", run_enas), ("hyperband", run_hyperband), ("pbt", run_pbt)
    ):
        wanted = (
            args.which == which
            or args.which == "all"
            or (args.which == "both" and which in ("enas", "hyperband"))
        )
        if not wanted:
            continue
        root = tempfile.mkdtemp(prefix=f"{which}-record-")
        ctrl = ExperimentController(root_dir=root)
        try:
            rec = runner(ctrl, args.timeout, scale, dataset=args.dataset)
            rec["platform"] = platform
            rec["device_kind"] = getattr(jax.devices()[0], "device_kind", platform)
            if which == "pbt":
                # protocol benchmark, not an image workload — the dataset
                # knob/provenance does not apply
                rec["dataset"] = (
                    "triangle-wave optimal-lr benchmark "
                    "(models/simple_pbt.py; reference "
                    "trial-images/simple-pbt/pbt_test.py)"
                )
                stem = f"{which}_{platform}"
            elif args.dataset == "digits":
                from katib_tpu.utils.datasets import DIGITS_PROVENANCE

                rec["dataset"] = DIGITS_PROVENANCE
                rec["dataset_is_real"] = True
                stem = f"{which}_{platform}_digits"
            else:
                from run_north_star import cifar10_provenance

                rec["dataset"] = cifar10_provenance()
                stem = f"{which}_{platform}"
            out = os.path.join(REPO, "examples", "records", f"{stem}.json")
            with open(out, "w") as f:
                json.dump(rec, f, indent=1)
            brief = {k: v for k, v in rec.items() if k != "trials"}
            print(json.dumps(brief, indent=1))
            print(f"record written to {out}", flush=True)
        except Exception as e:
            print(f"{which} record failed: {type(e).__name__}: {e}", flush=True)
            rc = 1
        finally:
            ctrl.close()
            shutil.rmtree(root, ignore_errors=True)
    raise SystemExit(rc)


if __name__ == "__main__":
    main()
