"""Native multi-fidelity search (ISSUE 11): ASHA rung ladders as a
scheduler citizen — pause-at-boundary, checkpoint-promoted rungs, drain
pruning — plus the satellite fixes (hyperband consult backoff, shared
curve reader, rung-aware pack keys, `katib-tpu rungs`).

The promotion-path coverage pins the load-bearing guarantees:
- a promoted trial RESUMES from its checkpoint bit-identically (same PRNG
  stream, observation log continuous, no duplicate rows);
- a corrupt (or missing) checkpoint degrades the promotion to a clean
  re-run-from-scratch (observation log restarted, never mixed);
- a trial killed while rung-paused stays killed and is never promoted.
"""

import math
import os
import time
from collections import Counter

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
from katib_tpu.api.status import Trial, TrialCondition
from katib_tpu.api.validation import ValidationError
from katib_tpu.config import KatibConfig
from katib_tpu.controller.experiment import ExperimentController
from katib_tpu.controller.multifidelity import (
    ALGORITHM_NAME,
    PAUSED_LABEL,
    RUNG_LABEL,
    FidelityLadder,
    MultiFidelityEngine,
    ladder_report,
    pack_rung_key,
)
from katib_tpu.db.store import fold_observation


def _quiet_config(**overrides):
    cfg = KatibConfig()
    cfg.runtime.telemetry = False
    cfg.runtime.compile_service = False
    for k, v in overrides.items():
        setattr(cfg.runtime, k, v)
    return cfg


def _asha_spec(name, fn, *, eta=2, max_resource=4, max_trials=8, parallel=4,
               seed="7", extra_settings=()):
    return ExperimentSpec(
        name=name,
        parameters=[
            ParameterSpec("x", ParameterType.DOUBLE, FeasibleSpace(min="0", max="1")),
            ParameterSpec(
                "epochs", ParameterType.INT,
                FeasibleSpace(min="1", max=str(max_resource)),
            ),
        ],
        objective=ObjectiveSpec(
            type=ObjectiveType.MAXIMIZE, objective_metric_name="score"
        ),
        algorithm=AlgorithmSpec(
            ALGORITHM_NAME,
            algorithm_settings=[
                AlgorithmSetting("eta", str(eta)),
                AlgorithmSetting("resource_name", "epochs"),
                AlgorithmSetting("random_state", seed),
                *extra_settings,
            ],
        ),
        trial_template=TrialTemplate(function=fn),
        max_trial_count=max_trials,
        parallel_trial_count=parallel,
    )


def _curve_fn(assignments, ctx):
    """Deterministic learning curve (higher x is better), checkpoint-resumed:
    each stint continues from its saved epoch to the assigned total budget."""
    x = float(assignments["x"])
    budget = int(float(assignments["epochs"]))
    store = ctx.checkpoint_store()
    restored = store.restore()
    start = int(restored["epoch"]) + 1 if restored else 1
    for epoch in range(start, budget + 1):
        store.save(epoch, {"epoch": epoch})
        ctx.report(score=x * math.log1p(epoch), epoch=epoch)


def _stream_replica(x, n):
    """Pure-python replica of _stream_fn's chained PRNG values."""
    key = int(x * 1e9) & ((1 << 62) - 1)
    out = []
    for _ in range(n):
        rng = np.random.default_rng(key)
        out.append(float(rng.random()))
        key = int(rng.integers(0, 2**62))
    return out


def _stream_fn(assignments, ctx):
    """Chained-PRNG trial: the stream key lives in the checkpoint, so a
    resumed stint continues the SAME stream — any restart or duplicate
    report diverges from the replica."""
    x = float(assignments["x"])
    budget = int(float(assignments["epochs"]))
    store = ctx.checkpoint_store()
    restored = store.restore()
    if restored is not None:
        epoch, key = int(restored["epoch"]), int(restored["key"])
    else:
        epoch, key = 0, int(x * 1e9) & ((1 << 62) - 1)
    while epoch < budget:
        rng = np.random.default_rng(key)
        val = float(rng.random())
        key = int(rng.integers(0, 2**62))
        epoch += 1
        store.save(epoch, {"epoch": epoch, "key": key})
        ctx.report(score=x + val * 1e-6, val=val, epoch=epoch)


def _wait_for(predicate, timeout=30.0, poll=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(poll)
    return False


@pytest.fixture
def controller(tmp_path):
    c = ExperimentController(
        root_dir=str(tmp_path), devices=list(range(4)), config=_quiet_config()
    )
    yield c
    c.close()


# -- ladder construction / validation ---------------------------------------


def test_ladder_from_spec_geometry():
    spec = _asha_spec("lad", _curve_fn, eta=3, max_resource=27)
    ladder = FidelityLadder.from_spec(spec)
    assert ladder.rungs == [1.0, 3.0, 9.0, 27.0]
    assert ladder.top == 3
    assert ladder.format(ladder.rungs[0]) == "1"  # INT resource truncates
    assert ladder.rung_of("9") == 2
    assert ladder.rung_of("27") == 3


def test_ladder_clips_to_max_resource():
    spec = _asha_spec("lad2", _curve_fn, eta=3, max_resource=20)
    ladder = FidelityLadder.from_spec(spec)
    assert ladder.rungs == [1.0, 3.0, 9.0, 20.0]


def test_asha_validation_errors():
    from katib_tpu.suggest.base import create

    suggester = create(ALGORITHM_NAME)
    base = _asha_spec("val", _curve_fn)

    missing = _asha_spec("val2", _curve_fn)
    missing.algorithm.algorithm_settings = [AlgorithmSetting("eta", "2")]
    with pytest.raises(ValueError, match="resource_name"):
        suggester.validate_algorithm_settings(missing)

    bad_eta = _asha_spec("val3", _curve_fn)
    for s in bad_eta.algorithm.algorithm_settings:
        if s.name == "eta":
            s.value = "1"
    with pytest.raises(ValueError, match="eta"):
        suggester.validate_algorithm_settings(bad_eta)

    no_budget = _asha_spec("val4", _curve_fn)
    no_budget.max_trial_count = None
    with pytest.raises(ValueError, match="maxTrialCount"):
        suggester.validate_algorithm_settings(no_budget)

    not_param = _asha_spec("val5", _curve_fn)
    for s in not_param.algorithm.algorithm_settings:
        if s.name == "resource_name":
            s.value = "nope"
    with pytest.raises(ValueError, match="parameter"):
        suggester.validate_algorithm_settings(not_param)

    suggester.validate_algorithm_settings(base)  # sane spec passes


# -- end-to-end ladder -------------------------------------------------------


def test_asha_e2e_ladder_structure_and_integrity(controller):
    c = controller
    spec = _asha_spec("asha-e2e", _curve_fn)
    c.create_experiment(spec)
    exp = c.run("asha-e2e", timeout=180)

    assert exp.status.is_succeeded, exp.status.message
    trials = c.state.list_trials("asha-e2e")
    assert len(trials) == 8  # every admitted configuration is one trial

    budgets = Counter(int(float(t.assignments_dict()["epochs"])) for t in trials)
    # eta=2, rungs 1/2/4 over 8 configs: 4 pruned at rung 0, 4 promoted;
    # 2 pruned at rung 1, 2 promoted; both survivors succeed at the top
    assert budgets == {1: 4, 2: 2, 4: 2}, budgets
    conds = Counter((t.condition.value, t.current_reason) for t in trials)
    assert conds[("Succeeded", "TrialSucceeded")] == 2, (conds, [(t.name, t.message) for t in trials])
    assert conds[("EarlyStopped", "RungPruned")] == 6

    ev = Counter(e.reason for e in c.events.list("asha-e2e"))
    assert ev["RungPromoted"] == 6
    assert ev["RungPruned"] == 6
    assert ev["RungPaused"] == 12  # 8 at rung 0 + 4 at rung 1

    # zero lost observations: every curve continuous from epoch 1, and the
    # fold index byte-identical to a raw row scan
    for t in trials:
        rows = c.obs_store.get_observation_log(t.name, metric_name="epoch")
        epochs = [int(float(r.value)) for r in rows]
        assert epochs == list(range(1, len(epochs) + 1)), (t.name, epochs)
        if t.condition == TrialCondition.SUCCEEDED:
            assert epochs[-1] == 4  # survivors saw the full budget
        fold = c.obs_store.folded(t.name, ["score", "epoch"]).to_dict()
        rescan = fold_observation(
            c.obs_store.get_observation_log(t.name), ["score", "epoch"]
        ).to_dict()
        assert fold == rescan, t.name

    # per-stint device-seconds were charged for the asha experiment
    spent = sum(
        v
        for (metric, _), v in c.metrics._counters.items()
        if metric == "katib_multifidelity_device_seconds"
    )
    assert spent > 0.0

    # nothing is left paused once the ladder drained
    assert all(PAUSED_LABEL not in t.labels for t in trials)

    report = ladder_report(exp.spec, trials, c.obs_store)
    pops = [r["population"] for r in report["rungs"]]
    assert pops == [8, 4, 2]
    assert [r["promoted"] for r in report["rungs"]] == [4, 2, 0]
    assert [r["pruned"] for r in report["rungs"]] == [4, 2, 0]
    assert report["rungs"][-1]["succeeded"] == 2


def test_rungs_cli_offline(controller, tmp_path, capsys):
    from katib_tpu import cli

    c = controller
    c.create_experiment(_asha_spec("asha-cli", _curve_fn, max_trials=4, eta=2))
    c.run("asha-cli", timeout=120)
    rc = cli.main(["--root", str(tmp_path), "rungs", "asha-cli"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "RUNG" in out and "PROMOTED" in out
    assert "resource=epochs" in out

    rc = cli.main(["--root", str(tmp_path), "rungs", "missing-exp"])
    assert rc == 1


# -- promotion path ----------------------------------------------------------


def test_promotion_resumes_bit_identical(controller):
    """The tentpole guarantee: a trial promoted through the ladder produces
    EXACTLY the value stream of an uninterrupted run — same chained PRNG
    sequence, observation log continuous, no duplicate rows."""
    c = controller
    spec = _asha_spec("asha-bits", _stream_fn, eta=2, max_resource=4)
    c.create_experiment(spec)
    exp = c.run("asha-bits", timeout=180)
    assert exp.status.is_succeeded, exp.status.message

    trials = c.state.list_trials("asha-bits")
    promoted = [t for t in trials if int(t.labels.get(RUNG_LABEL, "0")) > 0]
    assert promoted, "no trial was ever promoted"
    for t in trials:
        x = float(t.assignments_dict()["x"])
        rows = c.obs_store.get_observation_log(t.name, metric_name="val")
        got = [float(r.value) for r in rows]
        assert got == pytest.approx(_stream_replica(x, len(got)), abs=0.0), t.name
        epochs = [
            int(float(r.value))
            for r in c.obs_store.get_observation_log(t.name, metric_name="epoch")
        ]
        assert epochs == list(range(1, len(epochs) + 1)), t.name
    # the succeeded survivors trained across every rung of the ladder
    full = [t for t in trials if t.condition == TrialCondition.SUCCEEDED]
    assert full and all(
        len(c.obs_store.get_observation_log(t.name, metric_name="val")) == 4
        for t in full
    )


def _submit_solo(c, exp, name, x, budget):
    """Admit one asha trial straight through the scheduler (no reconcile
    loop), so rung state can be driven deterministically from the test."""
    from katib_tpu.api.spec import ParameterAssignment

    trial = Trial(
        name=name,
        experiment_name=exp.name,
        parameter_assignments=[
            ParameterAssignment("x", str(x)),
            ParameterAssignment("epochs", str(budget)),
        ],
    )
    c.state.create_trial(trial)
    c.scheduler.submit(exp, trial)
    return trial


def test_promotion_claim_unclaims_mid_transition_trial(controller):
    """Regression (ISSUE 14): a concurrent claimer can reach _promote_one
    while the boundary thread has registered the pause but not yet set the
    EarlyStopped condition. The claim used to be consumed (promoted set
    grown, paused entry popped) with no promotion — the trial ended the
    sweep stuck RungPaused. The claim must be RESTORED so a later pump
    promotes once the transition lands."""
    import contextlib

    from katib_tpu.api.spec import ParameterAssignment

    c = controller
    spec = _asha_spec("asha-race", _curve_fn, eta=2, max_resource=4, max_trials=4)
    exp = c.create_experiment(spec)
    engine = c.multifidelity
    st = engine._entry(exp)
    # two recorded boundary scores at rung 0 -> floor(2/2)=1 promotable
    names = ["asha-race-a", "asha-race-b"]
    for name, x in zip(names, ("0.9", "0.1")):
        trial = Trial(
            name=name, experiment_name="asha-race",
            parameter_assignments=[
                ParameterAssignment("x", x),
                ParameterAssignment("epochs", "1"),
            ],
        )
        # mid-transition shape: paused map + scores registered, but the
        # trial still reads Running (condition/labels not yet persisted)
        trial.set_condition(TrialCondition.RUNNING, "TrialRunning", "mid-boundary")
        c.state.create_trial(trial)
        st.brackets[0].scores[0][name] = float(x)
        st.paused[name] = (0, 0)

    submitted = []

    class FakeScheduler:
        workdir_root = None
        LINEAGE_LABEL = "checkpoint-lineage"

        def dispatch_barrier(self):
            return contextlib.nullcontext()

        def submit(self, exp, trial, checkpoint_dir=None, dispatch=True):
            submitted.append(trial.name)

    assert engine._maybe_promote(exp, FakeScheduler()) is False
    assert submitted == []
    # the claim was restored, not consumed
    assert st.paused.get("asha-race-a") == (0, 0)
    assert "asha-race-a" not in st.brackets[0].promoted[0]

    # the boundary transition lands; the next pump promotes normally
    best = c.state.get_trial("asha-race", "asha-race-a")
    best.labels[PAUSED_LABEL] = "0"
    best.labels[RUNG_LABEL] = "0"
    best.set_condition(TrialCondition.EARLY_STOPPED, "RungPaused", "paused")
    c.state.update_trial(best)
    assert engine._maybe_promote(exp, FakeScheduler()) is True
    assert submitted == ["asha-race-a"]
    assert "asha-race-a" in st.brackets[0].promoted[0]


def _paused(c, exp_name, trial_name):
    t = c.state.get_trial(exp_name, trial_name)
    return (
        t is not None
        and t.condition == TrialCondition.EARLY_STOPPED
        and t.current_reason == "RungPaused"
    )


def test_kill_during_pause_never_promotes(controller):
    c = controller
    # eta=3 over 2 trials: floor(2/3)=0 — nothing auto-promotes, so both
    # park in the paused state for the test to operate on
    spec = _asha_spec("asha-kill", _curve_fn, eta=3, max_resource=9, max_trials=8)
    exp = c.create_experiment(spec)
    _submit_solo(c, exp, "asha-kill-a", 0.9, 1)
    _submit_solo(c, exp, "asha-kill-b", 0.5, 1)
    assert _wait_for(lambda: _paused(c, "asha-kill", "asha-kill-a"))
    assert _wait_for(lambda: _paused(c, "asha-kill", "asha-kill-b"))

    c.scheduler.kill("asha-kill-a")
    t = c.state.get_trial("asha-kill", "asha-kill-a")
    assert t.condition == TrialCondition.KILLED
    assert PAUSED_LABEL not in t.labels

    eng = c.multifidelity
    st = eng._entry(exp)
    with eng._lock:
        assert "asha-kill-a" not in st.paused
        assert "asha-kill-b" in st.paused
        # its recorded score still informs the rung cut for its peers
        assert "asha-kill-a" in st.brackets[0].scores[0]
    assert eng._eligible_locked(st) == []  # killed trial is not a candidate


def test_corrupt_checkpoint_promotes_from_scratch(controller, tmp_path):
    import shutil

    c = controller
    spec = _asha_spec("asha-cor", _stream_fn, eta=3, max_resource=9, max_trials=8)
    exp = c.create_experiment(spec)
    _submit_solo(c, exp, "asha-cor-ok", 0.8, 1)
    _submit_solo(c, exp, "asha-cor-bad", 0.6, 1)
    assert _wait_for(lambda: _paused(c, "asha-cor", "asha-cor-ok"))
    assert _wait_for(lambda: _paused(c, "asha-cor", "asha-cor-bad"))
    first_row_time = {
        name: c.obs_store.get_observation_log(name, metric_name="val")[0].timestamp
        for name in ("asha-cor-ok", "asha-cor-bad")
    }

    # corrupt every checkpoint artifact of the bad trial
    bad_dir = os.path.join(str(tmp_path), "trials", "asha-cor", "asha-cor-bad")
    assert os.path.isdir(bad_dir)
    for entry in os.listdir(bad_dir):
        path = os.path.join(bad_dir, entry)
        if os.path.isdir(path):
            shutil.rmtree(path)
            os.makedirs(path)  # step dir exists but is empty = corrupt
        else:
            with open(path, "wb") as f:
                f.write(b"garbage")

    eng = c.multifidelity
    st = eng._entry(exp)
    for name in ("asha-cor-ok", "asha-cor-bad"):
        with eng._lock:
            st.paused.pop(name, None)
            st.brackets[0].promoted[0].add(name)
        assert eng._promote_one(
            exp, name, 0, 0, st.brackets[0].ladder, c.scheduler
        )
    assert _wait_for(lambda: _paused(c, "asha-cor", "asha-cor-ok"))
    assert _wait_for(lambda: _paused(c, "asha-cor", "asha-cor-bad"))

    for name, x in (("asha-cor-ok", 0.8), ("asha-cor-bad", 0.6)):
        rows = c.obs_store.get_observation_log(name, metric_name="val")
        got = [float(r.value) for r in rows]
        # both curves are complete, continuous, and replica-exact — the
        # corrupt one re-ran from scratch and reproduced the stream
        assert got == pytest.approx(_stream_replica(x, 3), abs=0.0), name
    # the intact trial RESUMED (its first stint's row survived); the corrupt
    # one re-ran from scratch (the log was dropped and re-reported)
    ok_rows = c.obs_store.get_observation_log("asha-cor-ok", metric_name="val")
    bad_rows = c.obs_store.get_observation_log("asha-cor-bad", metric_name="val")
    assert ok_rows[0].timestamp == first_row_time["asha-cor-ok"]
    assert bad_rows[0].timestamp > first_row_time["asha-cor-bad"]

    msgs = {
        e.name: e.message
        for e in c.events.list("asha-cor")
        if e.reason == "RungPromoted"
    }
    assert "resuming from checkpoint" in msgs["asha-cor-ok"]
    assert "re-running from scratch" in msgs["asha-cor-bad"]


def test_engine_rebuilds_from_persisted_state(controller):
    """A fresh engine (controller restart) reconstructs paused trials and
    rung scores from trial labels + the fold index."""
    c = controller
    spec = _asha_spec("asha-reb", _curve_fn, eta=3, max_resource=9, max_trials=8)
    exp = c.create_experiment(spec)
    _submit_solo(c, exp, "asha-reb-a", 0.9, 1)
    _submit_solo(c, exp, "asha-reb-b", 0.2, 1)
    assert _wait_for(lambda: _paused(c, "asha-reb", "asha-reb-a"))
    assert _wait_for(lambda: _paused(c, "asha-reb", "asha-reb-b"))

    fresh = MultiFidelityEngine(c.state, c.obs_store)
    st = fresh._entry(exp)
    assert st.paused == {"asha-reb-a": (0, 0), "asha-reb-b": (0, 0)}
    assert set(st.brackets[0].scores[0]) == {"asha-reb-a", "asha-reb-b"}
    assert st.brackets[0].scores[0]["asha-reb-a"] == pytest.approx(
        0.9 * math.log1p(1)
    )


# -- gating ------------------------------------------------------------------


def test_knob_off_rejects_asha(tmp_path):
    c = ExperimentController(
        root_dir=str(tmp_path),
        devices=list(range(4)),
        config=_quiet_config(multifidelity=False),
    )
    try:
        assert c.multifidelity is None
        assert c.scheduler.multifidelity is None
        with pytest.raises(ValidationError, match="multifidelity"):
            c.create_experiment(_asha_spec("asha-off", _curve_fn))
    finally:
        c.close()


def test_knob_off_keeps_hyperband_byte_identical(tmp_path):
    """The legacy stateless hyperband path must be untouched by the engine:
    the same seeded sweep produces the identical trial set with the
    multifidelity knob on and off, and the engine records nothing."""

    def hb_fn(assignments, ctx):
        x = float(assignments["x"])
        budget = float(assignments["budget"])
        ctx.report(score=x * math.log1p(budget))

    def hb_spec(name):
        return ExperimentSpec(
            name=name,
            parameters=[
                ParameterSpec("x", ParameterType.DOUBLE, FeasibleSpace(min="0", max="1")),
                ParameterSpec("budget", ParameterType.INT, FeasibleSpace(min="1", max="4")),
            ],
            objective=ObjectiveSpec(
                type=ObjectiveType.MAXIMIZE, objective_metric_name="score"
            ),
            algorithm=AlgorithmSpec(
                "hyperband",
                algorithm_settings=[
                    AlgorithmSetting("eta", "2"),
                    AlgorithmSetting("r_l", "4"),
                    AlgorithmSetting("resource_name", "budget"),
                    AlgorithmSetting("random_state", "13"),
                ],
            ),
            trial_template=TrialTemplate(function=hb_fn),
            max_trial_count=40,
            parallel_trial_count=4,
        )

    def run_once(sub, multifidelity):
        root = os.path.join(str(tmp_path), sub)
        c = ExperimentController(
            root_dir=root,
            devices=list(range(4)),
            config=_quiet_config(multifidelity=multifidelity),
        )
        try:
            name = f"hb-{sub}"
            c.create_experiment(hb_spec(name))
            exp = c.run(name, timeout=180)
            assert exp.status.is_succeeded, exp.status.message
            if c.multifidelity is not None:
                with c.multifidelity._lock:
                    assert c.multifidelity._exps == {}  # never consulted
            return sorted(
                (t.assignments_dict()["x"], t.assignments_dict()["budget"])
                for t in c.state.list_trials(name)
            )
        finally:
            c.close()

    assert run_once("on", True) == run_once("off", False)


# -- satellite: hyperband consult backoff ------------------------------------


def test_hyperband_consult_backoff_does_not_spin(tmp_path):
    """A rung of still-running trials must not re-run the child-bracket
    consult on every reconcile poll: after one TrialsNotCompleted the
    consult is held until a trial's condition (or the request) changes."""
    from katib_tpu.api.spec import Metric, Observation
    from katib_tpu.api.status import Experiment
    from katib_tpu.controller.suggestion import SuggestionService
    from katib_tpu.db.state import ExperimentStateStore
    from katib_tpu.db.store import InMemoryObservationStore

    spec = ExperimentSpec(
        name="hb-spin",
        parameters=[
            ParameterSpec("x", ParameterType.DOUBLE, FeasibleSpace(min="0", max="1")),
            ParameterSpec("budget", ParameterType.INT, FeasibleSpace(min="1", max="4")),
        ],
        objective=ObjectiveSpec(
            type=ObjectiveType.MAXIMIZE, objective_metric_name="score"
        ),
        algorithm=AlgorithmSpec(
            "hyperband",
            algorithm_settings=[
                AlgorithmSetting("eta", "2"),
                AlgorithmSetting("r_l", "4"),
                AlgorithmSetting("resource_name", "budget"),
                AlgorithmSetting("random_state", "3"),
            ],
        ),
        trial_template=TrialTemplate(function=lambda a, c: None),
        max_trial_count=40,
        parallel_trial_count=4,
    )
    state = ExperimentStateStore(None)
    svc = SuggestionService(state, InMemoryObservationStore())
    exp = Experiment(spec=spec)
    state.create_experiment(exp)

    suggester = svc.suggester_for(exp)
    calls = {"n": 0}
    orig = suggester.get_suggestions

    def counted(request):
        calls["n"] += 1
        return orig(request)

    suggester.get_suggestions = counted

    # master bracket: 4 new assignments
    served = svc.sync_assignments(exp, [], requests=4)
    assert len(served) == 4 and calls["n"] == 1

    trials = []
    for i, a in enumerate(served):
        t = Trial.from_assignment(a, "hb-spin")
        t.set_condition(TrialCondition.RUNNING, "TrialRunning", "")
        t.start_time = 100.0 + i
        trials.append(t)

    # the rung is running: the child-bracket consult answers "wait" ONCE...
    for _ in range(6):
        got = svc.sync_assignments(exp, trials, requests=8)
        assert got == []
    assert calls["n"] == 2, "consult was retried in a tight loop"

    # ...and a trial completing re-opens it via the changed signature
    for i, t in enumerate(trials):
        t.set_condition(TrialCondition.SUCCEEDED, "TrialSucceeded", "")
        t.observation = Observation(
            metrics=[Metric(name="score", latest=str(i), min=str(i), max=str(i))]
        )
    got = svc.sync_assignments(exp, trials, requests=8)
    assert calls["n"] == 3
    assert len(got) == 2  # top ceil(4/2)=2 survivors at the next budget


# -- satellite: shared curve reader ------------------------------------------


def test_medianstop_byte_identical_after_curve_reader_refactor():
    """Pin medianstop decisions to the pre-refactor inline logic: same
    first-start_step read (limit pushdown), same non-numeric skip, same
    mean-of-means rule value."""
    from katib_tpu.api.spec import EarlyStoppingSpec
    from katib_tpu.db.store import InMemoryObservationStore, MetricLog
    from katib_tpu.earlystop.medianstop import MedianStop

    store = InMemoryObservationStore()
    rows = {
        "t1": ["1.0", "2.0", "3.0", "99.0"],        # 4th row beyond start_step
        "t2": ["nan-ish", "4.0", "6.0"],            # non-numeric skipped
        "t3": ["bad", "worse", "awful"],            # no numeric value: ignored
        "t4": ["10.0"],
    }
    for name, values in rows.items():
        store.report_observation_log(
            name,
            [
                MetricLog(metric_name="score", value=v, timestamp=float(i))
                for i, v in enumerate(values)
            ],
        )

    spec = ExperimentSpec(
        name="ms",
        parameters=[
            ParameterSpec("x", ParameterType.DOUBLE, FeasibleSpace(min="0", max="1"))
        ],
        objective=ObjectiveSpec(
            type=ObjectiveType.MAXIMIZE, objective_metric_name="score"
        ),
        algorithm=AlgorithmSpec("random"),
        trial_template=TrialTemplate(function=lambda a, c: None),
        early_stopping=EarlyStoppingSpec(
            algorithm_name="medianstop",
            algorithm_settings=[
                AlgorithmSetting("min_trials_required", "2"),
                AlgorithmSetting("start_step", "3"),
            ],
        ),
    )
    trials = []
    for name in rows:
        t = Trial(name=name, experiment_name="ms")
        t.set_condition(TrialCondition.SUCCEEDED, "TrialSucceeded", "")
        trials.append(t)

    rules = MedianStop().get_early_stopping_rules(spec, trials, store)
    assert len(rules) == 1

    # frozen pre-refactor logic, inlined
    expected_avgs = []
    for name in rows:
        first = store.get_observation_log(name, metric_name="score", limit=3)
        values = []
        for log in first:
            try:
                values.append(float(log.value))
            except ValueError:
                continue
        if values:
            expected_avgs.append(sum(values) / len(values))
    expected = sum(expected_avgs) / len(expected_avgs)
    assert rules[0].value == str(expected)
    assert rules[0].name == "score"
    assert rules[0].start_step == 3


# -- satellite: rung-aware pack keys -----------------------------------------


def test_pack_rung_key_and_plan_packs_split_mixed_rungs():
    from katib_tpu.api.spec import ParameterAssignment, TrialResources
    from katib_tpu.api.status import Experiment
    from katib_tpu.controller.packing import plan_packs

    def fn(assignments, ctx):
        pass

    spec = _asha_spec("asha-pack", fn, eta=3, max_resource=9, max_trials=8)
    spec.trial_template.resources = TrialResources(pack_size=4)
    exp = Experiment(spec=spec)

    def trial(name, budget):
        return Trial(
            name=name,
            experiment_name="asha-pack",
            parameter_assignments=[
                ParameterAssignment("x", "0.5"),
                ParameterAssignment("epochs", str(budget)),
            ],
        )

    assert pack_rung_key(spec, trial("t", 3)) == "3"

    waiting = [
        (exp, trial("a", 1)),
        (exp, trial("b", 3)),
        (exp, trial("c", 1)),
        (exp, trial("d", 3)),
    ]
    units = plan_packs(waiting)
    shapes = sorted(
        tuple(sorted(t.name for t in members)) for _, members in units
    )
    # same-rung trials pack; rungs never mix even without a probe
    assert shapes == [("a", "c"), ("b", "d")]

    # non-asha experiments get a None rung key — legacy grouping unchanged
    plain = _asha_spec("plain", fn, max_trials=8)
    plain.algorithm.algorithm_name = "random"
    assert pack_rung_key(plain, trial("t", 3)) is None


# -- tentpole (ISSUE 13): dwell-window promotion packing ----------------------


def _pack_curve_fn(assignments, ctx):
    """Dual-mode (solo/packed) curve trial with per-member epoch
    checkpoints, so promoted stints resume in either mode."""
    import numpy as np

    from katib_tpu.runtime.checkpoints import CheckpointStore
    from katib_tpu.runtime.packed import (
        population_of,
        report_population,
        uniform_param,
    )

    pop = population_of(assignments)
    budget = int(uniform_param(pop, "epochs", 1))
    xs = pop["x"]
    if hasattr(ctx, "pack_size"):
        dirs = [cd or wd for cd, wd in zip(ctx.checkpoint_dirs, ctx.workdirs)]
        stores = [CheckpointStore(d) for d in dirs]
    else:
        stores = [ctx.checkpoint_store()]
    restored = [s.restore() for s in stores]
    start = min(int(r["epoch"]) + 1 if r else 1 for r in restored)
    for epoch in range(start, budget + 1):
        for s in stores:
            s.save(epoch, {"epoch": epoch})
        score = xs * (1.0 - np.exp(-epoch / 4.0))
        report_population(
            ctx, score=score, epoch=np.full(len(xs), float(epoch))
        )


DWELL_XS = (0.9, 0.8, 0.7, 0.6, 0.4, 0.3, 0.2, 0.1)


def _run_dwell_sweep(tmp_path, sub, dwell):
    """One packed asha sweep (rungs 1/2, 8 fixed configs admitted
    sequentially so the async claim order is deterministic, pack_size=4)
    under the given dwell window; returns (outcomes, promoted, events)."""
    from katib_tpu.api.spec import TrialResources

    root = os.path.join(str(tmp_path), sub)
    c = ExperimentController(
        root_dir=root,
        devices=list(range(4)),
        config=_quiet_config(promotion_dwell_seconds=dwell),
    )
    try:
        name = f"dw-{sub}"
        spec = _asha_spec(
            name, _pack_curve_fn, eta=2, max_resource=2, max_trials=8,
            parallel=4, seed="23",
        )
        spec.trial_template.resources = TrialResources(pack_size=4)
        exp = c.create_experiment(spec)
        for i, x in enumerate(DWELL_XS):
            _submit_solo(c, exp, f"{name}-t{i}", x, 1)
            # sequential boundaries: claim order (and hence the promoted
            # set at each async quota step) is identical across runs
            assert _wait_for(
                lambda t=f"{name}-t{i}": _paused(c, name, t)
                or c.state.get_trial(name, t).condition
                == TrialCondition.SUCCEEDED
            ), i
        exp = c.run(name, timeout=180)
        assert exp.status.is_succeeded, exp.status.message
        trials = c.state.list_trials(name)
        outcomes = sorted(
            (
                t.assignments_dict()["x"],
                t.assignments_dict()["epochs"],
                t.condition.value,
                t.current_reason,
            )
            for t in trials
        )
        promoted = {
            t.name for t in trials if int(t.labels.get(RUNG_LABEL, "0")) > 0
        }
        events = list(c.events.list(name))
        return outcomes, promoted, events
    finally:
        c.close()


def test_dwell_batches_promotions_into_packs(tmp_path):
    """The packed-promotion acceptance: with a dwell window the 4 same-rung
    promotions resubmit as ONE batch and dispatch as ceil(4/pack_capacity)
    = 1 vmapped pack — not 4 solo trickles — and the sweep outcome is
    byte-identical to the dwell-off run (the seeded on-vs-off assertion)."""
    on_out, on_promoted, on_events = _run_dwell_sweep(tmp_path, "on", 30.0)
    off_out, off_promoted, off_events = _run_dwell_sweep(tmp_path, "off", 0.0)

    # identical seeded outcomes: same configs, budgets, conditions
    assert on_out == off_out
    assert len(on_promoted) == 4

    # dwell off: byte-identical PR 11 behavior — no batching events at all
    assert not [e for e in off_events if e.reason == "PromotionBatched"]

    # dwell on: one batch covering every promotion...
    batched = [e for e in on_events if e.reason == "PromotionBatched"]
    assert len(batched) == 1, [e.message for e in batched]
    assert all(name in batched[0].message for name in on_promoted)

    # ...and the rung-1 stint dispatches as exactly ceil(4/4) = 1 pack of
    # promoted members (dispatch-group count, not promotion count)
    def _pack_members(e):
        return set(e.message.split(": ", 1)[1].split(", "))

    on_packs = [e for e in on_events if e.reason == "PackFormed"]
    promo_packs = [
        e for e in on_packs if _pack_members(e) == on_promoted
    ]
    assert len(promo_packs) == 1, [e.message for e in on_packs]


def test_dwell_chaos_revoke_boundary_and_batch_bit_identical(tmp_path):
    """The PR 11 x PR 12 seam: a chaos `revoke` strikes (a) a rung-0 stint
    right at its first boundary heartbeat and (b) a member of the
    mid-dwell promotion batch. Both convert to device-loss preemptions,
    resume on the surviving devices from their rung checkpoints, and the
    final value streams are BIT-identical to the chaos-free replica with
    zero lost observations."""
    from katib_tpu.utils import chaos

    # grants: A=1, B=2 (revoked -> resume=3), C=4, D=5; dwell flush then
    # submits the 2 promotions in claim order: A=6 (revoked -> resume=8),
    # B=7
    chaos.install(chaos.parse_plan("seed=3;revoke=2@1;revoke=6@1"))
    c = ExperimentController(
        root_dir=str(tmp_path),
        devices=list(range(4)),
        config=_quiet_config(promotion_dwell_seconds=30.0),
    )
    try:
        spec = _asha_spec(
            "asha-chaos", _stream_fn, eta=2, max_resource=4, max_trials=4,
            extra_settings=(AlgorithmSetting("min_resource", "2"),),
        )
        exp = c.create_experiment(spec)
        xs = {"a": 0.9, "b": 0.8, "c": 0.3, "d": 0.2}
        for suffix, x in xs.items():
            _submit_solo(c, exp, f"asha-chaos-{suffix}", x, 2)
            assert _wait_for(
                lambda s=suffix: _paused(c, "asha-chaos", f"asha-chaos-{s}")
            ), suffix

        # the drain rule fired at the last boundary (budget exhausted):
        # both promotions resubmitted as one mid-dwell batch
        def _done(name):
            t = c.state.get_trial("asha-chaos", name)
            return t is not None and t.condition == TrialCondition.SUCCEEDED

        assert _wait_for(lambda: _done("asha-chaos-a"), timeout=60)
        assert _wait_for(lambda: _done("asha-chaos-b"), timeout=60)

        batched = [
            e for e in c.events.list("asha-chaos")
            if e.reason == "PromotionBatched"
        ]
        assert len(batched) == 1
        assert "asha-chaos-a" in batched[0].message
        assert "asha-chaos-b" in batched[0].message
        lost = [
            e for e in c.events.list("asha-chaos") if e.reason == "DeviceLost"
        ]
        assert len(lost) == 2, [e.message for e in lost]

        from katib_tpu.db.store import fold_observation

        for suffix, x in xs.items():
            name = f"asha-chaos-{suffix}"
            n = 4 if suffix in ("a", "b") else 2
            rows = c.obs_store.get_observation_log(name, metric_name="val")
            got = [float(r.value) for r in rows]
            # bit-identical to the uninterrupted replica: the revoked
            # stints resumed their chained PRNG streams from the rung
            # checkpoints, losing nothing and re-reporting nothing
            assert got == pytest.approx(_stream_replica(x, n), abs=0.0), name
            epochs = [
                int(float(r.value))
                for r in c.obs_store.get_observation_log(
                    name, metric_name="epoch"
                )
            ]
            assert epochs == list(range(1, n + 1)), name
            fold = c.obs_store.folded(name, ["score", "epoch"]).to_dict()
            rescan = fold_observation(
                c.obs_store.get_observation_log(name), ["score", "epoch"]
            ).to_dict()
            assert fold == rescan, name
    finally:
        chaos.install(None)
        chaos.reset()
        c.close()
