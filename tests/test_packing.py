"""Vmapped trial packing (controller/packing.py + runtime/packed.py).

ISSUE-1 tentpole invariants:
- packed-vs-sequential parity: identical per-trial observation logs and
  terminal conditions for a deterministic train fn;
- early-stop of one member mid-pack freezes only that member;
- member failure (ctx.fail_member) fails only that member;
- pack formation rules: mixed templates never pack, non-scalar assignments
  and command templates fall back to the solo path;
- a PBT generation executes as one packed program with correct per-member
  exploit/explore lineage labels;
- satellites: adaptive subprocess poll backoff, TrialDevicesClamped event,
  katib_pack_* metrics.
"""

import time

import numpy as np
import pytest

from katib_tpu.api import (
    AlgorithmSetting,
    AlgorithmSpec,
    ExperimentSpec,
    FeasibleSpace,
    ObjectiveSpec,
    ObjectiveType,
    ParameterSpec,
    ParameterType,
    TrialTemplate,
)
from katib_tpu.api.spec import (
    CollectorKind,
    ComparisonType,
    EarlyStoppingRule,
    MetricsCollectorSpec,
    ParameterAssignment,
    TrialParameterSpec,
    TrialResources,
)
from katib_tpu.api.status import Experiment, Trial, TrialCondition
from katib_tpu.api.validation import ValidationError, validate_experiment
from katib_tpu.controller.experiment import ExperimentController
from katib_tpu.controller.packing import (
    PACK_LABEL,
    pack_capacity,
    plan_packs,
    stack_assignments,
    unpackable_reason,
)
from katib_tpu.controller.scheduler import TrialScheduler
from katib_tpu.db.state import ExperimentStateStore
from katib_tpu.db.store import InMemoryObservationStore
from katib_tpu.runtime.packed import population_of, report_population

pytestmark = pytest.mark.smoke


def deterministic_pack_fn(assignments, ctx=None):
    """Pack-aware deterministic workload: score_step = lr * (step+1)."""
    pop = population_of(assignments)
    lr = pop["lr"]
    for step in range(3):
        report_population(ctx, score=lr * (step + 1))


deterministic_pack_fn.supports_packing = True


def make_spec(name, pack_size, lrs, parallel=None, fn=deterministic_pack_fn):
    return ExperimentSpec(
        name=name,
        parameters=[
            ParameterSpec("lr", ParameterType.DISCRETE, FeasibleSpace(list=lrs))
        ],
        objective=ObjectiveSpec(
            type=ObjectiveType.MAXIMIZE, objective_metric_name="score"
        ),
        algorithm=AlgorithmSpec("grid"),
        trial_template=TrialTemplate(
            function=fn, resources=TrialResources(pack_size=pack_size)
        ),
        max_trial_count=len(lrs),
        parallel_trial_count=parallel or (len(lrs) if pack_size > 1 else 1),
    )


def run_and_collect(tmp_path, name, pack_size, lrs, fn=deterministic_pack_fn):
    ctrl = ExperimentController(root_dir=None, persist=False, devices=list(range(8)))
    try:
        ctrl.create_experiment(make_spec(name, pack_size, lrs, fn=fn))
        exp = ctrl.run(name, timeout=120)
        logs, conds, labels = {}, {}, {}
        for t in ctrl.state.list_trials(name):
            lr = t.assignments_dict()["lr"]
            logs[lr] = [
                (l.metric_name, l.value)
                for l in ctrl.obs_store.get_observation_log(t.name)
            ]
            conds[lr] = t.condition
            labels[lr] = dict(t.labels)
        return exp, logs, conds, labels, ctrl.metrics.render()
    finally:
        ctrl.close()


class TestPackedVsSequentialParity:
    def test_identical_logs_and_conditions(self, tmp_path):
        lrs = ["0.1", "0.2", "0.3", "0.4"]
        _, seq_logs, seq_conds, _, _ = run_and_collect(
            tmp_path, "seq-parity", 1, lrs
        )
        exp, pack_logs, pack_conds, labels, metrics = run_and_collect(
            tmp_path, "pack-parity", 4, lrs
        )
        assert exp.status.is_succeeded
        assert seq_logs == pack_logs  # bit-identical per-trial metric streams
        assert seq_conds == pack_conds
        assert all(PACK_LABEL in l for l in labels.values())
        assert 'katib_pack_formed_total{experiment="pack-parity"} 1.0' in metrics
        assert 'katib_trial_packed_total{experiment="pack-parity"} 4.0' in metrics
        assert 'katib_pack_occupancy{experiment="pack-parity"} 1.0' in metrics

    def test_mnist_packed_parity_small(self):
        """The packing invariant on a real model at small N: the vmapped
        MNIST-CNN population produces bit-identical objective metrics to
        solo runs of the same members."""
        from katib_tpu.models.mnist_cnn import run_mnist_trial_packed

        lrs = ["0.01", "0.05"]
        base = [
            ParameterSpec("num_train_examples", ParameterType.DISCRETE, FeasibleSpace(list=["128"])),
            ParameterSpec("batch_size", ParameterType.DISCRETE, FeasibleSpace(list=["64"])),
            ParameterSpec("conv1_channels", ParameterType.DISCRETE, FeasibleSpace(list=["4"])),
            ParameterSpec("conv2_channels", ParameterType.DISCRETE, FeasibleSpace(list=["8"])),
            ParameterSpec("hidden_size", ParameterType.DISCRETE, FeasibleSpace(list=["32"])),
        ]

        def run(name, pack_size):
            ctrl = ExperimentController(
                root_dir=None, persist=False, devices=list(range(4))
            )
            try:
                spec = ExperimentSpec(
                    name=name,
                    parameters=[
                        ParameterSpec("lr", ParameterType.DISCRETE, FeasibleSpace(list=lrs))
                    ] + base,
                    objective=ObjectiveSpec(
                        type=ObjectiveType.MAXIMIZE,
                        objective_metric_name="accuracy",
                        additional_metric_names=["loss"],
                    ),
                    algorithm=AlgorithmSpec("grid"),
                    trial_template=TrialTemplate(
                        entry_point="katib_tpu.models.mnist_cnn:run_mnist_trial_packed",
                        resources=TrialResources(pack_size=pack_size),
                    ),
                    max_trial_count=len(lrs),
                    parallel_trial_count=len(lrs) if pack_size > 1 else 1,
                )
                ctrl.create_experiment(spec)
                ctrl.run(name, timeout=300)
                return {
                    t.assignments_dict()["lr"]: sorted(
                        (l.metric_name, l.value)
                        for l in ctrl.obs_store.get_observation_log(t.name)
                    )
                    for t in ctrl.state.list_trials(name)
                }
            finally:
                ctrl.close()

        assert run("mnist-seq", 1) == run("mnist-pack", 2)


def _scheduler(devices=4):
    state = ExperimentStateStore(None)
    obs = InMemoryObservationStore()
    from katib_tpu.controller.events import EventRecorder, MetricsRegistry

    events, metrics = EventRecorder(), MetricsRegistry()
    sched = TrialScheduler(
        state, obs, devices=list(range(devices)), events=events, metrics=metrics
    )
    return state, obs, sched, events, metrics


def _submit_pack(state, sched, exp, trials):
    state.create_experiment(exp)
    for t in trials:
        state.create_trial(t)
        sched.submit(exp, t, dispatch=False)
    sched.dispatch()
    for _ in trials:
        sched.events.get(timeout=60)


def _trial(exp_name, name, lr):
    return Trial(
        name=name,
        experiment_name=exp_name,
        parameter_assignments=[ParameterAssignment("lr", lr)],
    )


class TestMemberMasking:
    def test_early_stop_one_member_mid_pack(self):
        """A member whose early-stopping rules trip mid-pack is frozen (its
        log ends at the tripping report) and finalizes EarlyStopped; the
        rest of the pack runs to completion."""
        state, obs, sched, _, _ = _scheduler()
        exp = Experiment(spec=make_spec("es-pack", 3, ["0.1", "0.2", "0.3"]))
        trials = [_trial("es-pack", f"es-{i}", lr) for i, lr in enumerate(["0.1", "0.2", "0.3"])]
        # only member 2 carries a rule; it trips at its second report
        # (scores 0.3, 0.6, 0.9 vs GREATER 0.35)
        trials[2].early_stopping_rules = [
            EarlyStoppingRule(name="score", value="0.35", comparison=ComparisonType.GREATER)
        ]
        _submit_pack(state, sched, exp, trials)

        done = {t.name: state.get_trial("es-pack", t.name) for t in trials}
        assert done["es-0"].condition == TrialCondition.SUCCEEDED
        assert done["es-1"].condition == TrialCondition.SUCCEEDED
        assert done["es-2"].condition == TrialCondition.EARLY_STOPPED
        # frozen at the tripping report: 2 entries vs 3 for the survivors
        assert len(obs.get_observation_log("es-2")) == 2
        assert len(obs.get_observation_log("es-0")) == 3
        assert len(obs.get_observation_log("es-1")) == 3

    def test_member_failure_is_isolated(self):
        """ctx.fail_member fails one member; pack-mates succeed."""

        def failing_member_fn(assignments, ctx=None):
            pop = population_of(assignments)
            lr = pop["lr"]
            if hasattr(ctx, "fail_member"):
                for i, v in enumerate(lr):
                    if v > 0.25:
                        ctx.fail_member(i, "synthetic member failure")
            for step in range(2):
                report_population(ctx, score=lr * (step + 1))

        failing_member_fn.supports_packing = True

        state, obs, sched, _, _ = _scheduler()
        exp = Experiment(
            spec=make_spec("fail-pack", 3, ["0.1", "0.2", "0.3"], fn=failing_member_fn)
        )
        trials = [_trial("fail-pack", f"f-{i}", lr) for i, lr in enumerate(["0.1", "0.2", "0.3"])]
        _submit_pack(state, sched, exp, trials)

        assert state.get_trial("fail-pack", "f-0").condition == TrialCondition.SUCCEEDED
        assert state.get_trial("fail-pack", "f-1").condition == TrialCondition.SUCCEEDED
        failed = state.get_trial("fail-pack", "f-2")
        assert failed.condition == TrialCondition.FAILED
        assert "synthetic member failure" in failed.message
        assert obs.get_observation_log("f-2") == []  # frozen before any report
        assert len(obs.get_observation_log("f-0")) == 2

    def test_pack_exception_fails_survivors_only(self):
        """An exception escaping the shared program fails every still-active
        member (no per-member blame exists), but a member already frozen by
        fail_member keeps its own FAILED message."""

        def exploding_fn(assignments, ctx=None):
            pop = population_of(assignments)
            if hasattr(ctx, "fail_member"):
                ctx.fail_member(0, "bad checkpoint")
            report_population(ctx, score=pop["lr"])
            raise RuntimeError("shared program exploded")

        exploding_fn.supports_packing = True

        state, obs, sched, _, _ = _scheduler()
        exp = Experiment(spec=make_spec("boom-pack", 2, ["0.1", "0.2"], fn=exploding_fn))
        trials = [_trial("boom-pack", f"b-{i}", lr) for i, lr in enumerate(["0.1", "0.2"])]
        _submit_pack(state, sched, exp, trials)
        t0 = state.get_trial("boom-pack", "b-0")
        t1 = state.get_trial("boom-pack", "b-1")
        assert t0.condition == TrialCondition.FAILED and "bad checkpoint" in t0.message
        assert t1.condition == TrialCondition.FAILED and "exploded" in t1.message

    def test_kill_one_member_mid_pack(self):
        """scheduler.kill on one member freezes it (KILLED) at its next
        report; the rest of the pack completes."""
        import threading

        release = threading.Event()

        def slow_fn(assignments, ctx=None):
            pop = population_of(assignments)
            report_population(ctx, score=pop["lr"])
            release.wait(timeout=30)
            for step in range(2):
                report_population(ctx, score=pop["lr"] * (step + 2))

        slow_fn.supports_packing = True

        state, obs, sched, _, _ = _scheduler()
        exp = Experiment(spec=make_spec("kill-pack", 2, ["0.1", "0.2"], fn=slow_fn))
        trials = [_trial("kill-pack", f"k-{i}", lr) for i, lr in enumerate(["0.1", "0.2"])]
        state.create_experiment(exp)
        for t in trials:
            state.create_trial(t)
            sched.submit(exp, t, dispatch=False)
        sched.dispatch()
        deadline = time.time() + 10
        while len(obs.get_observation_log("k-0")) < 1 and time.time() < deadline:
            time.sleep(0.01)
        sched.kill("k-1")
        release.set()
        for _ in trials:
            sched.events.get(timeout=60)
        assert state.get_trial("kill-pack", "k-0").condition == TrialCondition.SUCCEEDED
        assert state.get_trial("kill-pack", "k-1").condition == TrialCondition.KILLED
        assert len(obs.get_observation_log("k-0")) == 3
        # killed member froze at its first post-kill report (which is kept)
        assert len(obs.get_observation_log("k-1")) == 2


class TestPackFormation:
    def test_mixed_templates_never_pack(self):
        e1 = Experiment(spec=make_spec("exp-a", 4, ["0.1", "0.2"]))
        e2 = Experiment(spec=make_spec("exp-b", 4, ["0.3", "0.4"]))
        waiting = [
            (e1, _trial("exp-a", "a0", "0.1")),
            (e2, _trial("exp-b", "b0", "0.3")),
            (e1, _trial("exp-a", "a1", "0.2")),
            (e2, _trial("exp-b", "b1", "0.4")),
        ]
        units = plan_packs(waiting)
        assert [(e.name, [t.name for t in ts]) for e, ts in units] == [
            ("exp-a", ["a0", "a1"]),
            ("exp-b", ["b0", "b1"]),
        ]

    def test_pack_capped_at_k(self):
        e = Experiment(spec=make_spec("exp-k", 2, ["0.1"] * 5))
        waiting = [(e, _trial("exp-k", f"t{i}", "0.1")) for i in range(5)]
        units = plan_packs(waiting)
        assert [len(ts) for _, ts in units] == [2, 2, 1]

    def test_non_scalar_assignment_falls_back_solo(self):
        e = Experiment(spec=make_spec("exp-cat", 4, ["0.1", "0.2"]))
        good = _trial("exp-cat", "g", "0.1")
        bad = Trial(
            name="c",
            experiment_name="exp-cat",
            parameter_assignments=[ParameterAssignment("lr", "adamw")],
        )
        assert unpackable_reason(e, good) is None
        assert "not a runtime scalar" in unpackable_reason(e, bad)
        units = plan_packs([(e, good), (e, bad)])
        assert [len(ts) for _, ts in units] == [1, 1]

    def test_command_template_never_packs(self):
        spec = make_spec("exp-cmd", 4, ["0.1"])
        spec.trial_template = TrialTemplate(
            command=["echo", "ok"], resources=TrialResources(pack_size=1)
        )
        e = Experiment(spec=spec)
        assert "subprocess" in unpackable_reason(e, _trial("exp-cmd", "t", "0.1"))

    def test_auto_detected_packability(self):
        """supports_packing on the fn packs at AUTO_PACK_SIZE without the
        spec opt-in."""
        spec = make_spec("exp-auto", 1, ["0.1"])
        e = Experiment(spec=spec)
        from katib_tpu.controller.packing import AUTO_PACK_SIZE

        assert pack_capacity(e) == AUTO_PACK_SIZE
        assert unpackable_reason(e, _trial("exp-auto", "t", "0.1")) is None

    def test_stack_assignments(self):
        trials = [_trial("e", "t0", "0.1"), _trial("e", "t1", "0.25")]
        stacked = stack_assignments(trials)
        np.testing.assert_allclose(stacked["lr"], [0.1, 0.25], rtol=1e-6)

    def test_solo_trials_still_run_when_experiment_packs(self, tmp_path):
        """Strict fallback at the controller level: a categorical-parameter
        experiment with pack_size set runs every trial solo and succeeds."""

        def cat_fn(assignments, ctx):
            ctx.report(score=1.0 if assignments["opt"] == "a" else 2.0)

        ctrl = ExperimentController(root_dir=None, persist=False, devices=list(range(4)))
        try:
            spec = ExperimentSpec(
                name="cat-fallback",
                parameters=[
                    ParameterSpec(
                        "opt", ParameterType.CATEGORICAL, FeasibleSpace(list=["a", "b"])
                    )
                ],
                objective=ObjectiveSpec(
                    type=ObjectiveType.MAXIMIZE, objective_metric_name="score"
                ),
                algorithm=AlgorithmSpec("grid"),
                trial_template=TrialTemplate(
                    function=cat_fn, resources=TrialResources(pack_size=4)
                ),
                max_trial_count=2,
                parallel_trial_count=2,
            )
            ctrl.create_experiment(spec)
            exp = ctrl.run("cat-fallback", timeout=60)
            assert exp.status.trials_succeeded == 2
            rendered = ctrl.metrics.render()
            assert "katib_pack_formed_total" not in rendered
        finally:
            ctrl.close()


class TestPackedPBT:
    def test_pbt_generation_packs_with_lineage(self, tmp_path):
        """Acceptance: a PBT experiment with pack_size=8 completes e2e with
        correct per-member exploit/explore lineage labels, generations
        executing as packed programs."""
        from katib_tpu.suggest.pbt import GENERATION_LABEL, PARENT_LABEL

        ctrl = ExperimentController(root_dir=None, persist=False, devices=list(range(8)))
        try:
            spec = ExperimentSpec(
                name="pbt-packed",
                parameters=[
                    ParameterSpec(
                        "lr", ParameterType.DOUBLE, FeasibleSpace(min="0.0001", max="0.02")
                    )
                ],
                objective=ObjectiveSpec(
                    type=ObjectiveType.MAXIMIZE,
                    objective_metric_name="Validation-accuracy",
                ),
                algorithm=AlgorithmSpec(
                    "pbt",
                    algorithm_settings=[
                        AlgorithmSetting("n_population", "8"),
                        AlgorithmSetting("truncation_threshold", "0.25"),
                        AlgorithmSetting(
                            "suggestion_trial_dir", str(tmp_path / "pbt-ckpt")
                        ),
                    ],
                ),
                trial_template=TrialTemplate(
                    entry_point="katib_tpu.models.simple_pbt:run_pbt_trial_packed",
                    resources=TrialResources(pack_size=8),
                ),
                max_trial_count=24,
                parallel_trial_count=8,
            )
            ctrl.create_experiment(spec)
            exp = ctrl.run("pbt-packed", timeout=240)
            assert exp.status.is_succeeded, exp.status.message
            trials = ctrl.state.list_trials("pbt-packed")
            assert all(t.condition == TrialCondition.SUCCEEDED for t in trials)
            # generations advanced and trials were actually packed
            generations = {int(t.labels[GENERATION_LABEL]) for t in trials}
            assert max(generations) >= 1
            packed = [t for t in trials if PACK_LABEL in t.labels]
            assert packed, "no trial carries the pack label"
            # lineage: exploit/explore children name a parent of the
            # previous generation; a packed program never mixes generations
            uid_gen = {t.name: int(t.labels[GENERATION_LABEL]) for t in trials}
            children = [t for t in trials if t.labels.get(PARENT_LABEL)]
            assert children, "no exploit/explore lineage produced"
            for t in children:
                parent = t.labels[PARENT_LABEL]
                assert uid_gen[t.name] == uid_gen[parent] + 1
            for t in packed:
                pack_members = [
                    u for u in trials
                    if u.labels.get(PACK_LABEL) == t.labels[PACK_LABEL]
                ]
                assert len({int(u.labels[GENERATION_LABEL]) for u in pack_members}) == 1
            # checkpoint lineage flowed: some gen>=1 score beats every gen-0
            # score only if state accumulated; assert max improved
            def best(gen):
                vals = []
                for t in trials:
                    if int(t.labels[GENERATION_LABEL]) == gen and t.observation:
                        m = t.observation.metric("Validation-accuracy")
                        if m and m.max != "unavailable":
                            vals.append(float(m.max))
                return max(vals) if vals else 0.0

            assert best(max(generations)) > best(0)
        finally:
            ctrl.close()


class TestSpecAndValidation:
    def test_pack_size_round_trips(self):
        r = TrialResources(num_devices=2, pack_size=8)
        assert TrialResources.from_dict(r.to_dict()).pack_size == 8
        assert TrialResources.from_dict({"numDevices": 1}).pack_size == 1
        assert "packSize" not in TrialResources().to_dict()

    def test_pack_size_validation(self):
        spec = make_spec("bad-pack", 0, ["0.1"])
        with pytest.raises(ValidationError, match="packSize"):
            validate_experiment(spec)
        cmd = make_spec("cmd-pack", 4, ["0.1"])
        cmd.trial_template = TrialTemplate(
            command=["run", "--lr", "${trialParameters.lr}"],
            trial_parameters=[],
            resources=TrialResources(pack_size=4),
        )
        with pytest.raises(ValidationError, match="in-process"):
            validate_experiment(cmd)
        hosts = make_spec("hosts-pack", 4, ["0.1"])
        hosts.trial_template.resources.num_hosts = 2
        with pytest.raises(ValidationError):
            validate_experiment(hosts)


class TestSatellites:
    def test_devices_clamped_event(self):
        state, obs, sched, events, _ = _scheduler(devices=2)
        spec = make_spec("clamp-exp", 1, ["0.1"])
        spec.trial_template.resources.num_devices = 8
        spec.trial_template.resources.pack_size = 1
        spec.trial_template.function = lambda a, ctx: ctx.report(score=1.0)
        exp = Experiment(spec=spec)
        t = _trial("clamp-exp", "clamped", "0.1")
        state.create_experiment(exp)
        state.create_trial(t)
        sched.submit(exp, t)
        sched.events.get(timeout=30)
        reasons = [e.reason for e in events.list("clamp-exp")]
        assert "TrialDevicesClamped" in reasons

    def test_adaptive_poll_backoff(self):
        from katib_tpu.controller.executor import _AdaptivePoll

        p = _AdaptivePoll(0.1, backoff_after=30.0, maximum=1.0)
        t0 = time.time()
        assert p.next_delay(t0) == pytest.approx(0.1)
        # 30s of quiet -> exponential: 0.2, 0.4, 0.8, 1.0, 1.0 ...
        assert p.next_delay(t0 + 31) == pytest.approx(0.2)
        assert p.next_delay(t0 + 32) == pytest.approx(0.4)
        assert p.next_delay(t0 + 33) == pytest.approx(0.8)
        assert p.next_delay(t0 + 34) == pytest.approx(1.0)
        assert p.next_delay(t0 + 60) == pytest.approx(1.0)
        # activity resets to the base interval
        p.activity(t0 + 61)
        assert p.next_delay(t0 + 62) == pytest.approx(0.1)

    def test_poll_interval_override_disables_backoff(self):
        from katib_tpu.controller.executor import SubprocessExecutor

        ex = SubprocessExecutor(InMemoryObservationStore())
        assert ex._make_poll().adaptive is True
        ex.POLL_INTERVAL = 0.05  # instance override, as the scheduler sets it
        p = ex._make_poll()
        assert p.adaptive is False
        assert p.next_delay(time.time() + 3600) == pytest.approx(0.05)

    def test_subprocess_trial_still_collects_with_backoff(self, tmp_path):
        """A quiet-then-bursty subprocess trial completes and collects its
        metrics through the adaptive wait loop."""
        import sys

        ctrl = ExperimentController(root_dir=str(tmp_path), devices=list(range(2)))
        try:
            spec = ExperimentSpec(
                name="backoff-e2e",
                parameters=[
                    ParameterSpec(
                        "x", ParameterType.DISCRETE, FeasibleSpace(list=["1.5"])
                    )
                ],
                objective=ObjectiveSpec(
                    type=ObjectiveType.MAXIMIZE, objective_metric_name="score"
                ),
                algorithm=AlgorithmSpec("grid"),
                trial_template=TrialTemplate(
                    command=[
                        sys.executable,
                        "-c",
                        "print('score=${trialParameters.x}')",
                    ],
                    trial_parameters=[TrialParameterSpec(name="x", reference="x")],
                ),
                metrics_collector_spec=MetricsCollectorSpec(
                    collector_kind=CollectorKind.STDOUT
                ),
                max_trial_count=1,
                parallel_trial_count=1,
            )
            ctrl.create_experiment(spec)
            exp = ctrl.run("backoff-e2e", timeout=60)
            assert exp.status.trials_succeeded == 1
        finally:
            ctrl.close()
