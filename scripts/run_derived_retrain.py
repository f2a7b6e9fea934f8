"""Derived-network retraining for a recorded DARTS HPO experiment.

Reads a record produced by ``scripts/run_north_star.py``, re-runs the
bilevel search at the record's optimal hyperparameters to extract the
winning genotype, retrains the derived (discrete) network on the same
dataset, and appends a ``derived_retrain`` block to the record — the
reference's stage-2 flow (darts-cnn-cifar10 run_trial.py searches; a user
then trains the printed genotype), automated.

Usage: python scripts/run_derived_retrain.py [--record PATH]
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--record",
        default=os.path.join(REPO, "examples", "records", "darts_hpo_50trials_cpu.json"),
    )
    ap.add_argument("--epochs", type=int, default=None,
                    help="retrain epochs (default: 2x the search epochs)")
    ap.add_argument("--tpu", action="store_true")
    args = ap.parse_args()

    if not args.tpu:
        os.environ["JAX_PLATFORMS"] = "cpu"  # before anything imports jax
    else:
        # SAME dataset knobs as the search record this reproduces — taken
        # from the RECORD's own provenance string, not the repo's current
        # TPU-rung set (which may have been recalibrated since the record
        # was captured): stage 2 on a different-difficulty task would
        # extract a different genotype and append an accuracy incomparable
        # with the record's distribution. Must happen before any
        # katib_tpu.utils.datasets import (knobs are read there at import).
        import re

        with open(args.record) as f:
            _prov = json.load(f).get("dataset", "")
        _knobs = {
            "KATIB_TPU_SYNTH_NOISE": r"noise=([\d.]+)",
            "KATIB_TPU_SYNTH_DISTRACTOR": r"distractor=([\d.]+)",
            "KATIB_TPU_SYNTH_VARIANTS": r"variants=(\d+)",
            "KATIB_TPU_SYNTH_LABEL_NOISE": r"train_label_noise=([\d.]+)",
        }
        _parsed = {k: m.group(1) for k, pat in _knobs.items()
                   if (m := re.search(pat, _prov))}
        if _parsed:
            os.environ.update(_parsed)
        else:
            # real-CIFAR or legacy record with no knob provenance: fall
            # back to the current TPU-rung set (set-if-unset)
            from katib_tpu.utils.synth_calibration import apply_tpu_rung_knobs

            apply_tpu_rung_knobs()

    import jax

    if not args.tpu:
        jax.config.update("jax_platforms", "cpu")

    from katib_tpu.models.darts_trainer import (
        DARTS_HPO_DEFAULT_PRIMITIVES, DartsSearch, _search_and_report,
    )
    from katib_tpu.models.darts_derived import run_darts_retrain_trial
    from katib_tpu.utils.compilation import enable_compilation_cache
    from katib_tpu.utils.datasets import load_cifar10

    enable_compilation_cache()
    with open(args.record) as f:
        record = json.load(f)
    scale = dict(record["scale"])
    best = record["optimal_assignments"] or {}

    # stage 1: reproduce the winning search to extract its genotype
    settings = dict(scale)
    settings.update({k: float(v) for k, v in best.items()})
    n_train = settings.pop("num_train_examples")
    num_layers = settings.pop("num_layers")
    x, y = load_cifar10("train", n=n_train)
    half = len(x) // 2
    search = DartsSearch(
        primitives=list(DARTS_HPO_DEFAULT_PRIMITIVES),
        num_layers=num_layers,
        settings=settings,
    )
    class Capture:
        last = {}

        def report(self, **m):
            self.last = m

        def jax_devices(self):
            return jax.devices()[:1]

    steps_per_epoch = max(half // search.batch_size, 1)
    t0 = time.time()
    search.build(x.shape[1:], steps_per_epoch * search.num_epochs)
    # EXACTLY the trial's loop (_search_and_report interleaves train_epoch
    # and validate on one rng stream) — a hand-rolled loop would consume the
    # rng differently from epoch 2 on and extract a genotype the recorded
    # winner never produced
    search_acc = _search_and_report(
        search, (x[:half], y[:half]), (x[half:], y[half:]), Capture()
    )
    genotype = search.genotype()
    search_s = time.time() - t0

    # stage 2: retrain the discrete network from scratch
    ctx = Capture()
    retrain_epochs = args.epochs or 2 * int(scale["num_epochs"])
    t0 = time.time()
    run_darts_retrain_trial(
        {"genotype": json.dumps(genotype)},
        ctx,
        num_epochs=retrain_epochs,
        batch_size=int(scale["batch_size"]),
        init_channels=int(scale["init_channels"]),
        num_layers=num_layers,
        stem_multiplier=int(scale["stem_multiplier"]),
        num_train_examples=n_train,
        lr=float(best.get("w_lr", 0.025)),
        momentum=float(best.get("w_momentum", 0.9)),
    )
    retrain_s = time.time() - t0

    record["derived_retrain"] = {
        "search_val_acc": round(float(search_acc), 4),
        "genotype": genotype,
        "retrain_epochs": retrain_epochs,
        "retrain_val_acc": ctx.last.get("Validation-accuracy"),
        "retrain_train_loss": ctx.last.get("Train-loss"),
        "search_s": round(search_s, 1),
        "retrain_s": round(retrain_s, 1),
        "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    with open(args.record, "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps(record["derived_retrain"], indent=1, default=str))
    print(f"appended derived_retrain to {args.record}")


if __name__ == "__main__":
    main()
