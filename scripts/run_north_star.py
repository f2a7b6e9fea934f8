"""The literal 50-trial north-star experiment (BASELINE.json configs[4]):
a controller-driven DARTS HPO — TPE over the bilevel search's optimizer
hyperparameters — run through the FULL framework stack (suggestion
protocol, scheduler, collectors, status), with wall-clock and the
per-trial accuracy distribution recorded to
``examples/records/darts_hpo_50trials_<platform>.json``.

Because DartsSearch traces its hyperparameters, all 50 trials share ONE
compiled search step (reference counterpart: 50 pod launches of
examples/v1beta1/nas/darts-cpu.yaml, each recompiling from scratch).

Scale is platform-adaptive. The TPU scale gives each trial a 192-step
search budget (6 epochs x 4096 examples) on the calibrated discriminative
stand-in (utils/datasets.py): good optimizer settings reach high val-acc,
bad ones stay near chance, so the 50-trial distribution actually spreads —
the round-4 review found the previous task saturated at 1.0 and mandated
this recalibration. The CPU scale is reduced to keep 50 trials tractable
on this 1-core box; at that capacity the task is mostly unlearnable, so
CPU records show a thin spread just above chance (capacity-starved by
design, the TPU record is the evidence artifact). CIFAR-10: uses a real
npz via KATIB_TPU_CIFAR10 when present; otherwise the synthetic stand-in,
with the fetch failure reason recorded in the artifact.

Usage: python scripts/run_north_star.py [--trials N] [--out PATH]
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


def cifar10_provenance() -> str:
    path = os.environ.get("KATIB_TPU_CIFAR10")
    if path and os.path.exists(path):
        return f"real CIFAR-10 npz ({path})"
    from katib_tpu.utils.datasets import (
        SYNTH_DISTRACTOR, SYNTH_NOISE, SYNTH_TRAIN_LABEL_NOISE, SYNTH_VARIANTS,
    )

    return (
        "calibrated discriminative synthetic stand-in (utils/datasets.py: "
        f"noise={SYNTH_NOISE}, distractor={SYNTH_DISTRACTOR}, "
        f"variants={SYNTH_VARIANTS}, train_label_noise={SYNTH_TRAIN_LABEL_NOISE}) "
        "— real CIFAR-10 fetch blocked by zero-egress environment: urlopen "
        "'Name or service not known' for cs.toronto.edu (scripts/fetch_cifar10.py)"
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=50)
    ap.add_argument("--out", default=None)
    ap.add_argument("--timeout", type=float, default=3600.0)
    ap.add_argument(
        "--tpu", action="store_true",
        help="run on the accelerator backend (default forces CPU)",
    )
    args = ap.parse_args()

    if not args.tpu:
        os.environ["JAX_PLATFORMS"] = "cpu"  # before anything imports jax
    else:
        # the TPU rung runs the calibrated harder knob set, when populated
        # (set-if-unset, BEFORE datasets.py is imported anywhere), so the
        # 50-trial distribution discriminates instead of saturating — the
        # dataset provenance string records whatever values end up in force
        from katib_tpu.utils.synth_calibration import apply_tpu_rung_knobs

        apply_tpu_rung_knobs()

    import jax

    if not args.tpu:
        jax.config.update("jax_platforms", "cpu")

    from katib_tpu.utils.compilation import enable_compilation_cache

    enable_compilation_cache()
    platform = jax.devices()[0].platform
    on_tpu = platform != "cpu"
    if args.tpu and not on_tpu:
        # fail loudly: proceeding would
        # run the CPU scale with the harder TPU knob set already in the
        # environment and overwrite the default-knob CPU record series with
        # an incomparable artifact
        raise SystemExit(
            "--tpu requested but JAX initialized a CPU backend "
            "(accelerator init fell back); refusing to "
            "write a CPU record under the TPU knob set"
        )
    if on_tpu:
        # 192 search steps/trial: enough for good w_lr/momentum settings to
        # learn the calibrated task (CNN probe: ~0.96 reachable; tiny-scale
        # supernet at 4ch/192 steps measured 0.44) while bad settings stay
        # near chance — the spread the round-4 review required.
        scale = dict(num_epochs=6, num_train_examples=4096, batch_size=64,
                     init_channels=8, num_nodes=2, stem_multiplier=3,
                     num_layers=3)
    else:
        scale = dict(num_epochs=2, num_train_examples=1024, batch_size=64,
                     init_channels=2, num_nodes=1, stem_multiplier=1,
                     num_layers=2)

    from katib_tpu.api import (
        AlgorithmSpec, Distribution, ExperimentSpec, FeasibleSpace,
        ObjectiveSpec, ObjectiveType, ParameterSpec, ParameterType,
        TrialTemplate,
    )
    from katib_tpu.controller.experiment import ExperimentController
    from katib_tpu.utils.e2e_verify import verify_experiment_results

    def darts_hpo_trial(assignments, ctx):
        from katib_tpu.models.darts_trainer import run_darts_hpo_trial

        run_darts_hpo_trial(assignments, ctx, **scale)

    name = f"darts-hpo-{args.trials}trials"
    root = tempfile.mkdtemp(prefix="north-star-")
    ctrl = ExperimentController(root_dir=root)
    try:
        spec = ExperimentSpec(
            name=name,
            objective=ObjectiveSpec(
                type=ObjectiveType.MAXIMIZE,
                objective_metric_name="Validation-accuracy",
                additional_metric_names=["Train-loss"],
            ),
            algorithm=AlgorithmSpec("tpe"),
            parameters=[
                ParameterSpec(
                    "w_lr", ParameterType.DOUBLE,
                    FeasibleSpace(min="0.005", max="0.2",
                                  distribution=Distribution.LOG_UNIFORM),
                ),
                ParameterSpec(
                    "alpha_lr", ParameterType.DOUBLE,
                    FeasibleSpace(min="0.0001", max="0.01",
                                  distribution=Distribution.LOG_UNIFORM),
                ),
                ParameterSpec(
                    "w_momentum", ParameterType.DOUBLE,
                    FeasibleSpace(min="0.5", max="0.99"),
                ),
            ],
            trial_template=TrialTemplate(function=darts_hpo_trial),
            max_trial_count=args.trials,
            parallel_trial_count=1,
        )
        ctrl.create_experiment(spec)
        t0 = time.time()
        verification = "ok"
        try:
            exp = ctrl.run(name, timeout=args.timeout)
        except TimeoutError as e:
            # record what DID run — a partial artifact beats a lost hour
            verification = f"run timeout: {e}"
            exp = ctrl.state.get_experiment(name)
        wallclock = time.time() - t0
        if verification == "ok":
            try:
                verify_experiment_results(ctrl, exp)
            except Exception as e:
                verification = f"verification failed: {type(e).__name__}: {e}"

        trials = ctrl.state.list_trials(name)
        accs, per_trial = [], []
        for t in trials:
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
        opt = exp.status.current_optimal_trial
        # "verification" and "optimal_assignments" are a stable contract:
        # run_derived_retrain.py reads the record by
        # verification == "ok" and a non-null optimal_assignments
        record = {
            "experiment": name,
            "algorithm": "tpe",
            "n_trials": len(trials),
            "n_succeeded": exp.status.trials_succeeded,
            "wallclock_s": round(wallclock, 1),
            "seconds_per_trial": round(wallclock / max(len(trials), 1), 2),
            "platform": platform,
            "device_kind": getattr(jax.devices()[0], "device_kind", platform),
            "scale": scale,
            "dataset": cifar10_provenance(),
            "best_val_acc": max(accs) if accs else None,
            "median_val_acc": round(statistics.median(accs), 4) if accs else None,
            "acc_quartiles": [
                round(q, 4) for q in statistics.quantiles(accs, n=4)
            ] if len(accs) >= 4 else None,
            "optimal_assignments": {
                a.name: a.value for a in opt.parameter_assignments
            } if opt else None,
            "reason": exp.status.reason.value,
            "verification": verification,
            "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "trials": per_trial,
        }
        out = args.out or os.path.join(
            REPO, "examples", "records", f"darts_hpo_{args.trials}trials_{platform}.json"
        )
        os.makedirs(os.path.dirname(out), exist_ok=True)
        with open(out, "w") as f:
            json.dump(record, f, indent=1)
        brief = {k: v for k, v in record.items() if k != "trials"}
        print(json.dumps(brief, indent=1))
        print(f"record written to {out}")
    finally:
        ctrl.close()
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    main()
