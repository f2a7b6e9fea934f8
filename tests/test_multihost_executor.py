"""Multi-host trial execution through the controller (VERDICT round-2 item 2):
``TrialResources.num_hosts`` drives a MultiHostExecutor gang of worker
processes forming one jax.distributed system — the TPU-native counterpart of
the reference's gang-scheduled distributed trial CRDs
(examples/v1beta1/kubeflow-training-operator/mpijob-horovod.yaml).

Covers: (a) a real 2-host LM training trial end-to-end via
ExperimentController.run(); (b) deterministic gang failure when one worker
dies; (c) primary-only metric collection; (d) admission validation of
num_hosts.
"""

import os

import pytest

from katib_tpu.api import (
    AlgorithmSpec,
    ExperimentSpec,
    FeasibleSpace,
    ObjectiveSpec,
    ObjectiveType,
    ParameterSpec,
    ParameterType,
    TrialResources,
    TrialTemplate,
    ValidationError,
)
from katib_tpu.api.status import TrialCondition
from katib_tpu.controller.experiment import ExperimentController

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture()
def controller(tmp_path):
    c = ExperimentController(root_dir=str(tmp_path))
    yield c
    c.close()


def _cat(name, value):
    return ParameterSpec(name, ParameterType.CATEGORICAL, FeasibleSpace(list=[value]))


def test_two_host_lm_trial_e2e(controller):
    """A 2-host distributed LM training trial (katib_tpu.parallel.train
    multi-process init path: jit out_shardings over the 2-process mesh)
    driven end-to-end by the controller."""
    spec = ExperimentSpec(
        name="mh-lm",
        parameters=[
            ParameterSpec(
                "learning_rate", ParameterType.DOUBLE,
                FeasibleSpace(min="0.001", max="0.01"),
            ),
            _cat("embed_dim", "32"),
            _cat("num_layers", "1"),
            _cat("num_heads", "2"),
            _cat("num_steps", "5"),
            _cat("batch_size", "4"),
            _cat("seq_len", "16"),
            _cat("vocab_size", "64"),
        ],
        objective=ObjectiveSpec(type=ObjectiveType.MINIMIZE, objective_metric_name="loss"),
        algorithm=AlgorithmSpec("random"),
        trial_template=TrialTemplate(
            entry_point="katib_tpu.parallel.train:run_lm_trial",
            # clear the harness's 8-virtual-device XLA_FLAGS: each worker
            # contributes its own (single) CPU device to the global mesh
            env={"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""},
            resources=TrialResources(num_devices=1, num_hosts=2),
            retain=True,  # the test inspects host workdirs post-run
        ),
        max_trial_count=1,
        parallel_trial_count=1,
    )
    controller.create_experiment(spec)
    exp = controller.run("mh-lm", timeout=420)
    assert exp.status.is_succeeded, exp.status.message
    trial = controller.state.list_trials("mh-lm")[0]
    assert trial.condition == TrialCondition.SUCCEEDED, trial.message
    loss = trial.observation.metric("loss")
    assert loss is not None and loss.latest != "unavailable"
    assert float(loss.latest) > 0.0
    # both hosts actually ran
    trial_dir = os.path.join(controller.root_dir, "trials", "mh-lm", trial.name)
    assert os.path.exists(os.path.join(trial_dir, "host-0", "stdout.log"))
    assert os.path.exists(os.path.join(trial_dir, "host-1", "stdout.log"))


def test_worker_death_fails_gang_not_controller(controller):
    """Worker 1 exits 17 mid-trial: the trial (not the controller) must fail,
    worker 0 must be killed, and the experiment reaches its failure budget."""
    spec = ExperimentSpec(
        name="mh-crash",
        parameters=[
            ParameterSpec("x", ParameterType.DOUBLE, FeasibleSpace(min="0", max="1")),
        ],
        objective=ObjectiveSpec(type=ObjectiveType.MINIMIZE, objective_metric_name="loss"),
        algorithm=AlgorithmSpec("random"),
        trial_template=TrialTemplate(
            entry_point="gang_trial_helpers:crash_if_worker1",
            env={"JAX_PLATFORMS": "cpu", "PYTHONPATH": TESTS_DIR},
            resources=TrialResources(num_devices=1, num_hosts=2),
        ),
        max_trial_count=2,
        parallel_trial_count=1,
        max_failed_trial_count=0,
    )
    controller.create_experiment(spec)
    exp = controller.run("mh-crash", timeout=300)
    assert exp.status.is_completed and not exp.status.is_succeeded
    assert exp.status.reason.value == "ExperimentMaxFailedTrialsReached"
    trial = controller.state.list_trials("mh-crash")[0]
    assert trial.condition == TrialCondition.FAILED
    assert "exited with code 17" in trial.message
    assert "gang killed" in trial.message


def test_primary_only_metric_collection(controller):
    """Every worker reports, but observations come from process 0's stdout
    only (reference PrimaryPodLabels semantics) — no duplicate/off-by-rank
    metrics."""
    spec = ExperimentSpec(
        name="mh-primary",
        parameters=[
            ParameterSpec("x", ParameterType.DOUBLE, FeasibleSpace(min="0.5", max="0.5")),
        ],
        objective=ObjectiveSpec(type=ObjectiveType.MAXIMIZE, objective_metric_name="score"),
        algorithm=AlgorithmSpec("random"),
        trial_template=TrialTemplate(
            entry_point="gang_trial_helpers:report_and_exit",
            env={"JAX_PLATFORMS": "cpu", "PYTHONPATH": TESTS_DIR},
            resources=TrialResources(num_devices=1, num_hosts=2),
        ),
        max_trial_count=1,
        parallel_trial_count=1,
    )
    controller.create_experiment(spec)
    exp = controller.run("mh-primary", timeout=300)
    assert exp.status.is_succeeded, exp.status.message
    trial = controller.state.list_trials("mh-primary")[0]
    logs = controller.obs_store.get_observation_log(trial.name)
    values = [float(l.value) for l in logs if l.metric_name == "score"]
    # process 0 reports x + 0 = 0.5; process 1's 1.5 must NOT be collected
    assert values == [0.5], values


def test_concurrent_gangs_get_distinct_coordinators(controller):
    """Two 2-host gangs running in parallel must not collide on coordinator
    ports (executor _free_port tracks recently-issued ports) or cross-wire
    metric collection."""
    spec = ExperimentSpec(
        name="mh-parallel",
        parameters=[
            ParameterSpec("x", ParameterType.DOUBLE, FeasibleSpace(min="0.25", max="0.25")),
        ],
        objective=ObjectiveSpec(type=ObjectiveType.MAXIMIZE, objective_metric_name="score"),
        algorithm=AlgorithmSpec("random"),
        trial_template=TrialTemplate(
            entry_point="gang_trial_helpers:report_and_exit",
            env={"JAX_PLATFORMS": "cpu", "PYTHONPATH": TESTS_DIR},
            resources=TrialResources(num_devices=1, num_hosts=2),
        ),
        max_trial_count=4,
        parallel_trial_count=2,  # two gangs in flight at once
    )
    controller.create_experiment(spec)
    exp = controller.run("mh-parallel", timeout=300)
    assert exp.status.is_succeeded, exp.status.message
    trials = controller.state.list_trials("mh-parallel")
    assert len(trials) == 4
    for t in trials:
        assert t.condition == TrialCondition.SUCCEEDED, (t.name, t.message)
        logs = controller.obs_store.get_observation_log(t.name)
        values = [float(l.value) for l in logs if l.metric_name == "score"]
        assert values == [0.25], (t.name, values)  # own primary only


def test_num_hosts_validation(controller):
    base = dict(
        parameters=[
            ParameterSpec("x", ParameterType.DOUBLE, FeasibleSpace(min="0", max="1")),
        ],
        objective=ObjectiveSpec(type=ObjectiveType.MAXIMIZE, objective_metric_name="s"),
        algorithm=AlgorithmSpec("random"),
        max_trial_count=1,
    )
    with pytest.raises(ValidationError) as exc:
        controller.create_experiment(
            ExperimentSpec(
                name="mh-bad-fn",
                trial_template=TrialTemplate(
                    function=lambda a, c: None,
                    resources=TrialResources(num_hosts=2),
                ),
                **base,
            )
        )
    assert "numHosts" in str(exc.value)
    with pytest.raises(ValidationError):
        controller.create_experiment(
            ExperimentSpec(
                name="mh-bad-zero",
                trial_template=TrialTemplate(
                    entry_point="m:f", resources=TrialResources(num_hosts=0)
                ),
                **base,
            )
        )


def test_port_collision_relaunches_gang_without_restart(controller):
    """A worker dying on a coordinator bind-failure signature (the
    _free_port TOCTOU: an unrelated process stole the probed port) makes
    the executor relaunch the whole gang once on a fresh port — inside ONE
    trial execution, with max_trial_restarts untouched (0 here)."""
    spec = ExperimentSpec(
        name="mh-bind",
        parameters=[
            ParameterSpec("x", ParameterType.DOUBLE, FeasibleSpace(min="0", max="1")),
        ],
        objective=ObjectiveSpec(type=ObjectiveType.MAXIMIZE, objective_metric_name="score"),
        algorithm=AlgorithmSpec("random"),
        trial_template=TrialTemplate(
            entry_point="gang_trial_helpers:bind_fail_once",
            env={"JAX_PLATFORMS": "cpu", "PYTHONPATH": TESTS_DIR},
            resources=TrialResources(num_devices=1, num_hosts=2),
            retain=True,
        ),
        max_trial_count=1,
        parallel_trial_count=1,
    )
    controller.create_experiment(spec)
    exp = controller.run("mh-bind", timeout=300)
    assert exp.status.is_succeeded, exp.status.message
    trial = controller.state.list_trials("mh-bind")[0]
    assert trial.condition == TrialCondition.SUCCEEDED, trial.message
    assert float(trial.observation.metric("score").latest) == 1.0
    # no scheduler-level restart was consumed — the relaunch was internal
    assert not any(c.reason == "TrialRestarting" for c in trial.conditions)


@pytest.mark.parametrize(
    "env,n_hosts,holds,expect",
    [
        # CPU-held workers share nothing: never refused
        ({"JAX_PLATFORMS": "cpu"}, 2, True, None),
        # a controller that owns the chip cannot hand it to workers
        ({"JAX_PLATFORMS": "tpu"}, 1, True, "owns the chip"),
        # N workers on one TPU machine would each need all of its chips
        ({"JAX_PLATFORMS": "tpu"}, 2, False, "per-worker chip partitioning"),
        # a launcher that places workers on separate machines pins the coordinator
        ({"JAX_PLATFORMS": "tpu", "KATIB_TPU_COORDINATOR": "10.0.0.1:1234"}, 2, False, None),
        ({"JAX_PLATFORMS": "tpu"}, 1, False, None),
    ],
)
def test_gang_that_cannot_have_the_chip_is_refused_at_once(
    monkeypatch, env, n_hosts, holds, expect
):
    """One process for each chip: said before any worker starts, instead of
    workers waiting for each other in jax.distributed.initialize."""
    from katib_tpu.controller.executor import MultiHostExecutor
    from katib_tpu.utils import backend

    monkeypatch.setattr(backend, "holds_accelerator", lambda: holds)
    refusal = MultiHostExecutor._chip_refusal(dict(env), n_hosts)
    if expect is None:
        assert refusal is None
    else:
        assert expect in refusal
