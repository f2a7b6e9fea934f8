"""Calibrate the synthetic objective's difficulty knobs at the TPU rung.

Round-5 follow-up to the round-4 review's top item: the first recalibration
made the task discriminative at the BOTTOM of the hyperparameter range (bad
optimizer settings land 0.2-0.6) but the ceiling region stayed too wide —
at the TPU north-star scale (8-channel supernet, 192 search steps) any
decent w_lr reaches ~1.0, so an exploiting suggester (TPE) piles 44/50
trials onto a saturated objective and the quartiles degenerate again
(examples/records/darts_hpo_50trials_tpu.json, 2026-08-01 capture).

This script probes candidate KATIB_TPU_SYNTH_* knob sets by training the
exact north-star workload (run_darts_hpo_trial at the TPU scale) at three
fixed optimizer settings — good / mid / bad — and reports the val-acc each
reaches. The knobs are read at import, so every knob set runs in its own
subprocess. Pick the set where good ≈ 0.75-0.9 (ceiling below saturation),
mid lands mid-range, and bad stays near chance; wire the winner into
run_north_star.py's --tpu path as set-if-unset env defaults, and re-capture.

One process owns the chip at a time: this parent never imports JAX, and it
runs the children one after another, so each child has the chip to itself.

Usage: python scripts/calibrate_tpu_objective.py [--cpu] [--sets I,J,...]
"""

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (noise, distractor, variants) candidates, mildest first. train_label_noise
# stays 0 (the val split is carved out of the train split — see
# utils/datasets.py).
CANDIDATES = [
    (0.8, 0.5, 6),
    (1.0, 0.6, 6),
    (1.2, 0.7, 8),
    (1.5, 0.8, 8),
]

# optimizer settings spanning the north-star search space
# (w_lr 0.005-0.2 log, alpha_lr 1e-4-1e-2 log, momentum 0.5-0.99)
PROBES = {
    "good": {"w_lr": "0.15", "alpha_lr": "0.003", "w_momentum": "0.95"},
    "mid": {"w_lr": "0.02", "alpha_lr": "0.001", "w_momentum": "0.8"},
    "bad": {"w_lr": "0.006", "alpha_lr": "0.0003", "w_momentum": "0.6"},
}

# substituted via str.replace, NOT str.format — the body's literal {}
# braces would be eaten as positional placeholders
CHILD = r"""
import json, os, sys
sys.path.insert(0, __REPO__)
import jax  # --cpu: the parent set JAX_PLATFORMS=cpu in this child's env
from katib_tpu.utils.compilation import enable_compilation_cache
enable_compilation_cache()
from katib_tpu.models.darts_trainer import run_darts_hpo_trial

scale = dict(num_epochs=6, num_train_examples=4096, batch_size=64,
             init_channels=8, num_nodes=2, stem_multiplier=3, num_layers=3)

class Ctx:  # minimal report context: capture the metric stream
    def __init__(self):
        self.metrics = {}
    def report(self, **kw):
        for k, v in kw.items():
            self.metrics.setdefault(k, []).append(float(v))
    def jax_devices(self):
        return jax.devices()[:1]
    def should_stop(self):
        return False

probes = json.loads(os.environ["CALIB_PROBES"])
out = {}
for label, assignments in probes.items():
    ctx = Ctx()
    run_darts_hpo_trial(assignments, ctx, **scale)
    accs = ctx.metrics.get("Validation-accuracy", [])
    out[label] = max(accs) if accs else None
print("CALIB_RESULT " + json.dumps(out))
sys.stdout.flush()  # os._exit skips buffered-stdout flush
os._exit(0)
"""


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--sets", default=None,
                    help="comma-separated CANDIDATES indices (default: all)")
    ap.add_argument("--timeout", type=float, default=900.0)
    args = ap.parse_args()

    idxs = (
        [int(i) for i in args.sets.split(",")] if args.sets
        else range(len(CANDIDATES))
    )
    for i in idxs:
        noise, distractor, variants = CANDIDATES[i]
        env = dict(os.environ)
        if args.cpu:
            env["JAX_PLATFORMS"] = "cpu"
        env.update({
            "KATIB_TPU_SYNTH_NOISE": str(noise),
            "KATIB_TPU_SYNTH_DISTRACTOR": str(distractor),
            "KATIB_TPU_SYNTH_VARIANTS": str(variants),
            "CALIB_PROBES": json.dumps(PROBES),
        })
        t0 = time.time()
        try:
            proc = subprocess.run(
                [sys.executable, "-c", CHILD.replace("__REPO__", repr(REPO))],
                capture_output=True, text=True, timeout=args.timeout, env=env,
                cwd=REPO,
            )
        except subprocess.TimeoutExpired:
            print(f"set {i} noise={noise} distractor={distractor} "
                  f"variants={variants}: TIMEOUT {args.timeout:.0f}s", flush=True)
            continue
        result = None
        for line in reversed(proc.stdout.strip().splitlines()):
            if line.startswith("CALIB_RESULT "):
                result = json.loads(line[len("CALIB_RESULT "):])
                break
        if result is None:
            tail = (proc.stderr or proc.stdout or "").strip().splitlines()[-2:]
            print(f"set {i} noise={noise} distractor={distractor} "
                  f"variants={variants}: rc={proc.returncode} {' | '.join(tail)[-200:]}",
                  flush=True)
            continue
        print(
            f"set {i} noise={noise} distractor={distractor} variants={variants}: "
            + " ".join(f"{k}={v:.3f}" if v is not None else f"{k}=?"
                       for k, v in result.items())
            + f"  ({time.time() - t0:.0f}s)",
            flush=True,
        )


if __name__ == "__main__":
    main()
