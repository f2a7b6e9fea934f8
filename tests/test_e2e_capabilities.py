"""Per-capability end-to-end experiments, mirroring the reference's e2e CI
workflows (SURVEY.md §4: one workflow per capability — darts-cifar10,
enas-cifar10, simple-pbt, tf-mnist-with-summaries, pytorch-mnist matrix,
early stopping) at CI scale on synthetic data. Each test runs the FULL stack:
controller -> suggestion -> scheduler -> trial entry point -> metrics ->
status/optimal-trial assertions (run-e2e-experiment.py:17-120 checks).
"""


import numpy as np
import pytest

from katib_tpu.api import (
    AlgorithmSetting,
    AlgorithmSpec,
    EarlyStoppingSpec,
    ExperimentSpec,
    FeasibleSpace,
    GraphConfig,
    MetricsCollectorSpec,
    NasConfig,
    NasOperation,
    ObjectiveSpec,
    ObjectiveType,
    ParameterSpec,
    ParameterType,
    SourceSpec,
    TrialTemplate,
)
from katib_tpu.api.spec import CollectorKind
from katib_tpu.api.status import TrialCondition
from katib_tpu.controller.experiment import ExperimentController


@pytest.fixture()
def controller(tmp_path):
    c = ExperimentController(root_dir=str(tmp_path))
    yield c
    c.close()


def _tiny_darts(assignments, ctx):
    from katib_tpu.models.darts_trainer import run_darts_trial_scaled

    run_darts_trial_scaled(
        assignments, ctx,
        num_epochs=1, num_train_examples=64, batch_size=16, init_channels=2,
        num_nodes=2, stem_multiplier=1,
    )


def test_darts_e2e(controller):
    """e2e-test-darts-cifar10 equivalent at CI scale."""
    spec = ExperimentSpec(
        name="darts-e2e",
        objective=ObjectiveSpec(
            type=ObjectiveType.MAXIMIZE, objective_metric_name="Validation-accuracy"
        ),
        algorithm=AlgorithmSpec("darts"),
        nas_config=NasConfig(
            graph_config=GraphConfig(num_layers=2, input_sizes=[32, 32, 3], output_sizes=[10]),
            operations=[
                NasOperation("skip_connection"),
                NasOperation("max_pooling_3x3"),
            ],
        ),
        trial_template=TrialTemplate(function=_tiny_darts),
        max_trial_count=1,
        parallel_trial_count=1,
    )
    controller.create_experiment(spec)
    exp = controller.run("darts-e2e", timeout=420)
    assert exp.status.is_succeeded, exp.status.message
    opt = exp.status.current_optimal_trial
    acc = float(opt.observation.metric("Validation-accuracy").max)
    assert 0.0 <= acc <= 1.0
    # reference e2e invariants (run-e2e-experiment.py:17-120)
    from katib_tpu.utils.e2e_verify import verify_experiment_results

    verify_experiment_results(controller, exp)


def _tiny_enas(assignments, ctx):
    from katib_tpu.models.enas_child import run_enas_trial

    run_enas_trial(
        {**assignments, "num_epochs": "1", "num_train_examples": "48", "batch_size": "24"},
        ctx,
    )


def _tiny_darts_hpo(assignments, ctx):
    from katib_tpu.models.darts_trainer import run_darts_hpo_trial

    run_darts_hpo_trial(
        assignments, ctx,
        num_epochs=1, num_train_examples=64, batch_size=16, init_channels=2,
        num_nodes=1, stem_multiplier=1, num_layers=2,
    )


def test_darts_hpo_multitrial_e2e(controller):
    """The north-star shape: an HPO algorithm (tpe) searching the DARTS
    bilevel trainer's optimizer hyperparameters across multiple trials
    (scripts/run_north_star.py runs this at learning scale)."""
    from katib_tpu.api import Distribution

    spec = ExperimentSpec(
        name="darts-hpo-e2e",
        objective=ObjectiveSpec(
            type=ObjectiveType.MAXIMIZE,
            objective_metric_name="Validation-accuracy",
            additional_metric_names=["Train-loss"],
        ),
        algorithm=AlgorithmSpec("tpe"),
        parameters=[
            ParameterSpec(
                "w_lr", ParameterType.DOUBLE,
                FeasibleSpace(min="0.005", max="0.2", distribution=Distribution.LOG_UNIFORM),
            ),
            ParameterSpec(
                "alpha_lr", ParameterType.DOUBLE,
                FeasibleSpace(min="0.0001", max="0.01", distribution=Distribution.LOG_UNIFORM),
            ),
            ParameterSpec(
                "w_momentum", ParameterType.DOUBLE, FeasibleSpace(min="0.5", max="0.99"),
            ),
        ],
        trial_template=TrialTemplate(function=_tiny_darts_hpo),
        max_trial_count=2,
        parallel_trial_count=1,
    )
    controller.create_experiment(spec)
    exp = controller.run("darts-hpo-e2e", timeout=420)
    assert exp.status.is_succeeded, exp.status.message
    trials = controller.state.list_trials("darts-hpo-e2e")
    assert len(trials) == 2
    # every trial got distinct hyperparameter assignments and reported
    assignments = {tuple(sorted(t.assignments_dict().items())) for t in trials}
    assert len(assignments) == 2
    from katib_tpu.utils.e2e_verify import verify_experiment_results

    verify_experiment_results(controller, exp)


def test_enas_e2e(controller):
    """e2e-test-enas-cifar10 equivalent: REINFORCE controller suggests
    architectures, child networks train and report accuracy."""
    spec = ExperimentSpec(
        name="enas-e2e",
        objective=ObjectiveSpec(
            type=ObjectiveType.MAXIMIZE, objective_metric_name="Validation-accuracy"
        ),
        algorithm=AlgorithmSpec(
            "enas",
            algorithm_settings=[AlgorithmSetting("controller_train_steps", "2")],
        ),
        nas_config=NasConfig(
            graph_config=GraphConfig(num_layers=2, input_sizes=[32, 32, 3], output_sizes=[10]),
            operations=[
                NasOperation(
                    "convolution",
                    [
                        ParameterSpec(
                            "filter_size", ParameterType.CATEGORICAL, FeasibleSpace(list=["3"])
                        ),
                        ParameterSpec(
                            "num_filter", ParameterType.CATEGORICAL, FeasibleSpace(list=["8"])
                        ),
                    ],
                ),
                NasOperation(
                    "reduction",
                    [
                        ParameterSpec(
                            "reduction_type",
                            ParameterType.CATEGORICAL,
                            FeasibleSpace(list=["max_pooling"]),
                        )
                    ],
                ),
            ],
        ),
        trial_template=TrialTemplate(function=_tiny_enas),
        max_trial_count=2,
        parallel_trial_count=1,
    )
    controller.create_experiment(spec)
    exp = controller.run("enas-e2e", timeout=420)
    assert exp.status.is_succeeded, exp.status.message
    assert exp.status.trials_succeeded == 2
    trials = controller.state.list_trials("enas-e2e")
    for t in trials:
        assert "architecture" in t.assignments_dict()


def test_simple_pbt_e2e(controller):
    """e2e-test-simple-pbt equivalent: population evolves, checkpoints flow
    parent -> child through the lineage dirs, objective improves across
    generations."""
    from katib_tpu.models.simple_pbt import run_pbt_trial

    spec = ExperimentSpec(
        name="pbt-e2e",
        parameters=[
            ParameterSpec("lr", ParameterType.DOUBLE, FeasibleSpace(min="0.0001", max="0.02"))
        ],
        objective=ObjectiveSpec(
            type=ObjectiveType.MAXIMIZE, objective_metric_name="Validation-accuracy"
        ),
        algorithm=AlgorithmSpec(
            "pbt",
            algorithm_settings=[
                AlgorithmSetting("n_population", "5"),
                AlgorithmSetting("truncation_threshold", "0.5"),
            ],
        ),
        trial_template=TrialTemplate(function=run_pbt_trial),
        max_trial_count=15,
        parallel_trial_count=5,
    )
    controller.create_experiment(spec)
    exp = controller.run("pbt-e2e", timeout=180)
    assert exp.status.is_succeeded, exp.status.message
    trials = controller.state.list_trials("pbt-e2e")
    generations = {
        int(t.labels.get("pbt.katib-tpu/generation", "0")) for t in trials
    }
    assert max(generations) >= 1, f"population never advanced: {generations}"
    # later generations should carry forward accumulated score (checkpoints)
    by_gen = {}
    for t in trials:
        if t.observation is None:
            continue
        m = t.observation.metric("Validation-accuracy")
        if m is None:
            continue
        g = int(t.labels.get("pbt.katib-tpu/generation", "0"))
        by_gen.setdefault(g, []).append(float(m.max))
    last = max(by_gen)
    assert max(by_gen[last]) > max(by_gen[0])


def _plateau_trial(assignments, ctx):
    lr = float(assignments["lr"])
    # lr >= 0.5: improving learner; lr < 0.5: plateaus at a bad value that
    # declines with lr, so each later bad trial sits strictly below the mean
    # of earlier ones (the rule comparison is strict LESS — identical
    # plateaus would only trip via float rounding of the mean)
    for step in range(10):
        value = (0.1 + 0.08 * step) if lr >= 0.5 else (0.05 - lr / 100)
        ctx.report(**{"accuracy": value})


@pytest.mark.smoke
def test_medianstop_e2e(controller):
    """Early-stopping workflow: plateauing trials are stopped once the
    median rule is established by good trials."""
    spec = ExperimentSpec(
        name="medianstop-e2e",
        parameters=[
            ParameterSpec(
                "lr", ParameterType.DOUBLE, FeasibleSpace(min="0", max="1", step="0.142")
            )
        ],
        objective=ObjectiveSpec(type=ObjectiveType.MAXIMIZE, objective_metric_name="accuracy"),
        algorithm=AlgorithmSpec("grid"),
        early_stopping=EarlyStoppingSpec(
            "medianstop",
            [AlgorithmSetting("min_trials_required", "2"), AlgorithmSetting("start_step", "3")],
        ),
        trial_template=TrialTemplate(function=_plateau_trial),
        max_trial_count=8,
        parallel_trial_count=2,
    )
    controller.create_experiment(spec)
    exp = controller.run("medianstop-e2e", timeout=120)
    trials = controller.state.list_trials("medianstop-e2e")
    stopped = [t for t in trials if t.condition == TrialCondition.EARLY_STOPPED]
    succeeded = [t for t in trials if t.condition == TrialCondition.SUCCEEDED]
    assert stopped, "no trial was early stopped"
    assert succeeded, "no trial succeeded"
    # experiment still terminates with an optimal trial from the good half
    best = exp.status.current_optimal_trial
    assert float(best.observation.metric("accuracy").max) > 0.5


def test_tfevent_e2e(controller, tmp_path):
    """tf-mnist-with-summaries equivalent: subprocess trial writes real
    tfevents files (masked-crc framing), TfEvent collector extracts them."""
    trial_py = (
        "import sys\n"
        "sys.path.insert(0, '/root/repo')\n"
        "lr = float('${trialParameters.lr}')\n"
        "from katib_tpu.runtime.tfevent import write_scalar_events\n"
        "write_scalar_events('events', [(i, {'accuracy': lr * (i + 1) / 5.0}) for i in range(5)])\n"
    )
    from katib_tpu.api import TrialParameterSpec

    spec = ExperimentSpec(
        name="tfevent-e2e",
        parameters=[
            ParameterSpec("lr", ParameterType.DOUBLE, FeasibleSpace(min="0.5", max="1.0"))
        ],
        objective=ObjectiveSpec(type=ObjectiveType.MAXIMIZE, objective_metric_name="accuracy"),
        algorithm=AlgorithmSpec("random"),
        trial_template=TrialTemplate(
            command=["python", "-c", trial_py],
            trial_parameters=[TrialParameterSpec(name="lr", reference="lr")],
        ),
        metrics_collector_spec=MetricsCollectorSpec(
            collector_kind=CollectorKind.TF_EVENT,
            source=SourceSpec(file_path="events"),
        ),
        max_trial_count=2,
        parallel_trial_count=2,
    )
    controller.create_experiment(spec)
    exp = controller.run("tfevent-e2e", timeout=120)
    assert exp.status.is_succeeded, exp.status.message
    for t in controller.state.list_trials("tfevent-e2e"):
        assert t.condition == TrialCondition.SUCCEEDED
        m = t.observation.metric("accuracy")
        assert m is not None
        lr = float(t.assignments_dict()["lr"])
        assert abs(float(m.max) - lr) < 1e-5  # step 5: lr * 5/5


def test_pytorch_subprocess_e2e(controller):
    """The reference's pytorch-mnist matrix, as katib-tpu keeps it: a trial
    is an arbitrary subprocess in any ML framework (here genuine CPU torch,
    examples/trial_scripts/torch_mlp.py) with placeholder substitution and
    StdOut TEXT metric scraping — the framework-agnostic contract
    (README.md:27-31 of the reference)."""
    import json
    import os

    pytest.importorskip("torch")  # not a katib-tpu dependency; trial-side only
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "examples", "pytorch-subprocess.json")) as f:
        spec = ExperimentSpec.from_dict(json.load(f))
    # the shipped example assumes cwd == repo root; pin it for the test and
    # shrink the budget (torch import is ~5s per trial on this box)
    spec.trial_template.working_dir = repo
    spec.max_trial_count = 4
    spec.parallel_trial_count = 2
    spec.objective.goal = None  # assert on MaxTrialsReached determinism
    controller.create_experiment(spec)
    exp = controller.run(spec.name, timeout=300)
    assert exp.status.is_succeeded, exp.status.message
    trials = controller.state.list_trials(spec.name)
    assert len(trials) == 4
    assert all(t.condition == TrialCondition.SUCCEEDED for t in trials), [
        (t.name, t.condition.value, t.message) for t in trials
    ]
    best = exp.status.current_optimal_trial
    acc = float(best.observation.metric("accuracy").latest)
    assert 0.0 < acc <= 1.0
    # every trial scraped both metrics from stdout
    for t in trials:
        assert t.observation.metric("accuracy") is not None
        assert t.observation.metric("loss") is not None


def test_real_digits_hpo_e2e(controller):
    """The real-data axis through the full stack: the shipped digits-HPO
    experiment (scripts/run_digits_hpo.py — REAL UCI handwritten digits via
    sklearn, not the synthetic stand-in) searched by bayesopt's default
    gp_hedge portfolio, verified by the reference e2e invariants.

    Reference counterpart: hp-tuning CI on real MNIST
    (examples/v1beta1/hp-tuning/bayesian-optimization.yaml)."""
    import os
    import sys

    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "scripts")
    )
    from run_digits_hpo import build_spec

    from katib_tpu.utils.e2e_verify import verify_experiment_results

    spec = build_spec("digits-e2e", trials=3, parallel=1, epochs=2)
    controller.create_experiment(spec)
    exp = controller.run("digits-e2e", timeout=240)
    assert exp.status.is_succeeded, exp.status.message
    verify_experiment_results(controller, exp)
    trials = controller.state.list_trials("digits-e2e")
    accs = [
        float(t.observation.metric("Validation-accuracy").max) for t in trials
    ]
    assert len(accs) == 3
    # real data: accuracy is a genuine held-out number, not a ceiling pin
    assert all(0.0 <= a <= 1.0 for a in accs)
    best = exp.status.current_optimal_trial
    assert float(best.observation.metric("Validation-accuracy").max) == max(accs)
