"""The step ledger carries a trial's own counters (``ctx.count``): summed into
the open report interval's row under their names, after the fixed fields; a
trial that counts nothing writes the rows it always wrote."""

import pytest

from katib_tpu.tracing import StepLedger

from test_step_ledger import Clock, _interval


def test_counters_are_summed_into_the_interval_that_is_open():
    clock = Clock()
    ledger = StepLedger(clock=clock, wall=clock)
    ledger.count({"landed": 1.0})        # before the first report: the compile span's, dropped
    ledger.reported(clock())
    for landed, load_max in ((32000.0, 300.0), (33000.0, 310.0)):
        ledger.count({"landed": landed, "load_max": load_max})
        ledger.count({"landed": 1.0})
        _interval(ledger, clock)
    attrs = ledger.attrs()
    assert attrs["interval_fields"] == list(StepLedger.FIELDS) + ["landed", "load_max"]
    rows = [dict(zip(attrs["interval_fields"], row)) for row in attrs["intervals"]]
    assert [(r["landed"], r["load_max"]) for r in rows] == [(32001.0, 300.0), (33001.0, 310.0)]
    assert attrs["landed"] == 65002.0 and attrs["load_max"] == 610.0
    assert all(r["steps"] == 5 for r in rows)


def test_a_trial_that_counts_nothing_writes_the_rows_it_wrote():
    clock = Clock()
    ledger = StepLedger(clock=clock, wall=clock)
    ledger.reported(clock())
    _interval(ledger, clock)
    attrs = ledger.attrs()
    assert attrs["interval_fields"] == list(StepLedger.FIELDS)
    assert len(attrs["intervals"][0]) == len(StepLedger.FIELDS)
    assert set(attrs) == set(StepLedger.FIELDS[1:]) | {"interval_fields", "intervals"}


def test_run_lm_trial_counts_a_routed_model_s_loads_into_the_ledger(tmp_path):
    """Through the controller, as the benchmark's cell runs: the trial's
    ``steps`` span carries landed / load_max / load_mean per interval, and the
    dense trial's does not."""
    import json
    import os

    from katib_tpu.api.spec import experiment_spec_from_mapping
    from katib_tpu.controller.experiment import ExperimentController

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    arch = os.path.join(root, "benchmarks", "configs", "tiny-sparse.json")

    def one_value(name, value):
        return {"name": name, "parameterType": "discrete", "feasibleSpace": {"list": [value]}}

    def run(name, sizes):
        ctrl = ExperimentController(root_dir=str(tmp_path / name), devices=None)
        try:
            ctrl.create_experiment(experiment_spec_from_mapping({
                "name": name,
                "parameters": [{"name": "learning_rate", "parameterType": "double",
                                "feasibleSpace": {"min": "1e-4", "max": "1e-3"}}]
                + [one_value(k, v) for k, v in dict(sizes, seq_len="32", batch_size="2", num_steps="15").items()],
                "objective": {"type": "minimize", "objectiveMetricName": "loss"},
                "algorithm": {"algorithmName": "random", "algorithmSettings": []},
                "trialTemplate": {"entryPoint": "katib_tpu.parallel.train:run_lm_trial", "trialParameters": [],
                                  "resources": {"numDevices": 1, "numHosts": 1}},
                "maxTrialCount": 1, "parallelTrialCount": 1, "maxFailedTrialCount": 0}))
            exp = ctrl.run(name, timeout=600)
            assert exp.status.trials_succeeded == 1, exp.status
        finally:
            ctrl.close()
        folder = tmp_path / name / "traces" / name
        spans = json.loads(next(folder.iterdir()).read_text())["spans"]
        return next(s for s in spans if s["name"] == "steps")["attrs"]

    routed = run("routed", {"architecture": arch})
    assert routed["interval_fields"][-3:] == ["landed", "load_max", "load_mean"]
    rows = [dict(zip(routed["interval_fields"], r)) for r in routed["intervals"]]
    assert len(rows) == 2 and all(r["steps"] == 5 for r in rows)
    for r in rows:  # 4 routed layers of 4 held experts: the mean load is the landed over 16
        assert 0 < r["landed"] <= 4 * 64 * 3 and r["load_mean"] == pytest.approx(r["landed"] / 4)
        assert r["load_mean"] / 4 <= r["load_max"] / 4 <= 64
    dense = run("dense", {"vocab_size": "64", "embed_dim": "32", "num_layers": "1", "num_heads": "2"})
    assert dense["interval_fields"] == list(StepLedger.FIELDS)


def test_a_gang_worker_s_context_runs_a_routed_model_and_counts_nothing(capsys):
    """host_worker's WorkerContext keeps no ledger: ``count`` is there and does
    nothing, so a routed architecture reports from a gang worker as a dense one does."""
    import os

    from katib_tpu.parallel.train import run_lm_trial
    from katib_tpu.runtime.host_worker import WorkerContext

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assignments = {"architecture": os.path.join(root, "benchmarks", "configs", "tiny-sparse.json"),
                   "seq_len": "32", "batch_size": "2", "num_steps": "5", "learning_rate": "1e-3"}
    ctx = WorkerContext("t", "e", assignments, None, None, process_id=0, num_processes=1)
    assert ctx.count(landed=1.0) is None
    run_lm_trial(assignments, ctx)
    assert capsys.readouterr().out.startswith("loss=")
