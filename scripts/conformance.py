"""Conformance runner — the reference's conformance program, TPU-native.

The reference ships ``conformance/run.sh`` + ``Dockerfile.conformance``:
run one example experiment end-to-end (random search), tee the log, and
drop a done-file so the harness can collect the report. Same contract
here, minus the istio/namespace plumbing that has no analogue:

  python scripts/conformance.py                      # examples/random.json
  python scripts/conformance.py --experiment-path examples/tpe.json \
      --set num_train_examples=512 --set num_epochs=1 --max-trials 4

``--set name=value`` appends a single-value categorical parameter to the
spec, so every trial receives it as an assignment — the knob the reference
turns with pod annotations/env to shrink conformance workloads for CI.

Outputs in --outdir (default /tmp):
  katib-tpu-conformance.log    run log
  katib-tpu-conformance.json   report {experiment, pass, trials, best, ...}
  katib-tpu-conformance.done   done-file (reference run.sh contract)
Exit code 0 iff the experiment succeeded AND the e2e verifier passed.
"""

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--experiment-path",
                    default=os.path.join(REPO, "examples", "random.json"))
    ap.add_argument("--set", dest="overrides", action="append", default=[],
                    metavar="NAME=VALUE",
                    help="inject a fixed assignment into every trial")
    ap.add_argument("--max-trials", type=int, default=None)
    ap.add_argument("--parallel", type=int, default=None)
    ap.add_argument("--timeout", type=float, default=1200.0)
    ap.add_argument("--outdir", default=tempfile.gettempdir())
    ap.add_argument("--tpu", action="store_true",
                    help="run on the accelerator (default forces CPU)")
    args = ap.parse_args()

    if not args.tpu:
        os.environ["JAX_PLATFORMS"] = "cpu"  # before anything imports jax
    import jax

    if not args.tpu:
        jax.config.update("jax_platforms", "cpu")

    os.makedirs(args.outdir, exist_ok=True)
    log_path = os.path.join(args.outdir, "katib-tpu-conformance.log")
    report_path = os.path.join(args.outdir, "katib-tpu-conformance.json")
    done_path = os.path.join(args.outdir, "katib-tpu-conformance.done")
    for p in (log_path, report_path, done_path):
        try:
            os.unlink(p)
        except OSError:
            pass

    # Streamed like the reference's tee: every line hits the file as it is
    # printed, so a harness SIGKILL mid-run still leaves a diagnosable log.
    log_file = open(log_path, "a")

    def log(msg: str) -> None:
        print(msg, flush=True)
        log_file.write(msg + "\n")
        log_file.flush()

    from katib_tpu.api import FeasibleSpace, ParameterSpec, ParameterType
    from katib_tpu.api.spec import ExperimentSpec
    from katib_tpu.controller.experiment import ExperimentController
    from katib_tpu.utils.e2e_verify import verify_experiment_results

    with open(args.experiment_path) as f:
        spec = ExperimentSpec.from_dict(json.load(f))
    for ov in args.overrides:
        name, _, value = ov.partition("=")
        if not value:
            raise SystemExit(f"--set wants NAME=VALUE, got {ov!r}")
        spec.parameters.append(
            ParameterSpec(name, ParameterType.CATEGORICAL, FeasibleSpace(list=[value]))
        )
    if args.max_trials is not None:
        spec.max_trial_count = args.max_trials
        # keep the budget admissible: every shipped example carries
        # maxFailedTrialCount=3, which validation requires <= maxTrialCount
        if spec.max_failed_trial_count is not None:
            spec.max_failed_trial_count = min(
                spec.max_failed_trial_count, args.max_trials
            )
    if args.parallel is not None:
        spec.parallel_trial_count = args.parallel

    log(f"conformance: {os.path.relpath(args.experiment_path, REPO)} "
        f"({spec.algorithm.algorithm_name}, maxTrials={spec.max_trial_count}) "
        f"on {jax.devices()[0].platform}")
    root = tempfile.mkdtemp(prefix="conformance-")
    ctrl = ExperimentController(root_dir=root)
    passed, failure = False, None
    t0 = time.time()
    try:
        ctrl.create_experiment(spec)
        exp = ctrl.run(spec.name, timeout=args.timeout)
        log(f"experiment finished: {exp.status.condition.value} "
            f"({exp.status.reason.value}) in {time.time() - t0:.1f}s")
        verify_experiment_results(ctrl, exp)
        log("e2e verifier: ok")
        passed = exp.status.is_succeeded
        trials = ctrl.state.list_trials(spec.name)
        opt = exp.status.current_optimal_trial
        report = {
            "experiment": spec.name,
            "algorithm": spec.algorithm.algorithm_name,
            "platform": jax.devices()[0].platform,
            "pass": passed,
            "wallclock_s": round(time.time() - t0, 1),
            "trials": len(trials),
            "trials_succeeded": exp.status.trials_succeeded,
            "best_trial": opt.best_trial_name if opt else None,
            "optimal_assignments": {a.name: a.value for a in opt.parameter_assignments}
            if opt else None,
            "reason": exp.status.reason.value,
            "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        }
    except Exception as e:
        failure = f"{type(e).__name__}: {e}"
        log(f"conformance FAILED: {failure}")
        report = {
            "experiment": spec.name,
            "pass": False,
            "error": failure,
            "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        }
    finally:
        ctrl.close()

    with open(report_path, "w") as f:
        json.dump(report, f, indent=1)
    with open(done_path, "w") as f:  # reference run.sh done-file contract
        f.write("done\n")
    log(f"report: {report_path}")
    log_file.close()
    return 0 if report.get("pass") else 1


if __name__ == "__main__":
    raise SystemExit(main())
