"""Step-level performance plane (ISSUE 20): per-step timing, MFU accounting,
and the retrace/straggler/regression detectors.

Covers the tentpole's three layers plus the satellites:

- the runtime :class:`StepClock` (wall + deterministic counter clock, window
  flushing, external fused-chunk timing, compile/retrace accounting, the
  ``KATIB_TPU_STEP_STATS_INJECT`` fault seam);
- the reserved ``katib-tpu/perf/`` namespace: spec validation rejects
  objective/metric names under it, and the fold chokepoint
  (``ObservationStore.folded`` reads only requested names) keeps perf rows
  out of objective folding, warm-start signatures and BOHB rung models —
  pinned here by a seeded on-vs-off sweep whose folded observations, spans
  and warm-start history are identical;
- the controller :class:`StepStatsPlane`: stint rows through the observation
  pipeline, /metrics rollups, and the RetraceStorm / GangStraggler /
  StepTimeRegression detectors;
- MFU accounting (analysis/costmodel.py): per-backend peak-FLOPs table and
  the ``mfu()`` ratio;
- knob off (the default) is byte-identical: zero perf rows, no step metric
  families on /metrics, identical span set;
- SIGKILL failover (the PR 15 replica harness): a failed-over trial's perf
  series is continuous and bit-identical to a fault-free run under the
  deterministic counter clock;
- the ``katib-tpu perf`` offline CLI, the fleet-view perf folding, and the
  profileDir stamp on the trial root span (``katib-tpu trace``).
"""

import json
import os
import signal
import subprocess
import sys
import tempfile
import time

import pytest

from katib_tpu.api.spec import (
    AlgorithmSpec,
    ExperimentSpec,
    FeasibleSpace,
    ObjectiveSpec,
    ObjectiveType,
    ParameterSpec,
    ParameterType,
    TrialResources,
    TrialTemplate,
)
from katib_tpu.api.validation import ValidationError, validate_experiment
from katib_tpu.config import KatibConfig
from katib_tpu.controller.events import EventRecorder, MetricsRegistry
from katib_tpu.controller.experiment import ExperimentController
from katib_tpu.controller.stepstats import StepStatsPlane
from katib_tpu.db.store import InMemoryObservationStore, MetricLog
from katib_tpu.runtime.stepstats import (
    ENV_CLOCK,
    ENV_FLUSH_STEPS,
    ENV_INJECT,
    ENV_STEP_STATS,
    PERF_PREFIX,
    StepClock,
    _percentile,
    env_perf_logs,
    perf_logs,
    summarize_perf_rows,
)

pytestmark = pytest.mark.smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def counter_clock(monkeypatch):
    monkeypatch.setenv(ENV_CLOCK, "counter")
    monkeypatch.delenv(ENV_INJECT, raising=False)


def _spec(name, fn, n_trials=2, parallel=1, pack_size=None, retain=False,
          extra_metrics=()):
    tmpl = dict(function=fn)
    if pack_size:
        tmpl["resources"] = TrialResources(pack_size=pack_size)
    if retain:
        tmpl["retain"] = True
    return ExperimentSpec(
        name=name,
        parameters=[
            ParameterSpec(
                "x", ParameterType.DISCRETE,
                FeasibleSpace(list=[str(round(0.1 * (i + 1), 1))
                                    for i in range(n_trials)]),
            )
        ],
        objective=ObjectiveSpec(
            type=ObjectiveType.MAXIMIZE, objective_metric_name="score",
            additional_metric_names=list(extra_metrics),
        ),
        algorithm=AlgorithmSpec("grid"),
        trial_template=TrialTemplate(**tmpl),
        max_trial_count=n_trials,
        parallel_trial_count=parallel,
    )


def _perf_rows(ctrl, exp_name):
    """{trial_name: [(metric, value), ...]} restricted to the perf namespace."""
    out = {}
    for t in ctrl.state.list_trials(exp_name):
        out[t.name] = [
            (l.metric_name, l.value)
            for l in ctrl.obs_store.get_observation_log(t.name)
            if l.metric_name.startswith(PERF_PREFIX)
        ]
    return out


# -- the step clock -----------------------------------------------------------


class TestStepClock:
    def test_percentile_nearest_rank(self):
        assert _percentile([], 0.95) == 0.0
        assert _percentile([3.0], 0.5) == 3.0
        vals = [float(i) for i in range(1, 101)]
        assert _percentile(vals, 0.50) == 50.0
        assert _percentile(vals, 0.95) == 95.0
        assert _percentile([1.0, 2.0, 3.0], 0.95) == 3.0

    def test_wall_clock_skips_compile_boundary(self, monkeypatch):
        monkeypatch.delenv(ENV_CLOCK, raising=False)
        monkeypatch.delenv(ENV_INJECT, raising=False)
        c = StepClock(flush_steps=100)
        for _ in range(6):
            c.mark()
        rows, s = c.finalize()
        # the first mark closes the compile stretch — 6 reports, 5 steps
        assert s.steps == 5

    def test_counter_clock_every_mark_is_one_second(self, counter_clock):
        c = StepClock(flush_steps=2)
        for _ in range(5):
            c.mark({"examples": 10})
        rows = c.drain()
        # two completed windows of two 1.0s steps each
        assert rows == [
            ("step_seconds_mean", 1.0), ("step_seconds_p95", 1.0),
            ("steps_per_second", 1.0), ("examples_per_second", 10.0),
            ("step_seconds_mean", 1.0), ("step_seconds_p95", 1.0),
            ("steps_per_second", 1.0), ("examples_per_second", 10.0),
        ]
        final_rows, s = c.finalize()
        assert ("stint_step_seconds_p50", 1.0) in final_rows
        assert ("stint_step_seconds_p95", 1.0) in final_rows
        assert s.steps == 5 and s.seconds == 5.0 and s.examples == 50.0
        assert s.steps_per_second == 1.0

    def test_volume_keys_harvested_not_consumed(self, counter_clock):
        c = StepClock(flush_steps=1)
        metrics = {"score": 0.5, "tokens": 128}
        c.mark(metrics)
        assert metrics == {"score": 0.5, "tokens": 128}  # read, never popped
        rows = dict(c.drain())
        assert rows["examples_per_second"] == 128.0

    def test_note_steps_switches_to_external_mode(self, counter_clock):
        c = StepClock(flush_steps=100)
        c.note_steps(4, 8.0)
        c.mark({"examples": 5})  # demux-time report: volume only, no step
        _, s = c.finalize()
        assert s.steps == 4
        assert s.seconds == 4.0  # counter mode: 1.0 per external step too
        assert s.examples == 5.0

    def test_retraces_are_compiles_past_first(self):
        c = StepClock()
        assert c.retraces == 0
        c.note_compile()
        assert c.retraces == 0  # the initial compile is the expected cost
        c.note_compile()
        c.note_compile()
        assert c.retraces == 2

    def test_inject_retrace_fires_n_synthetic_retraces(self, monkeypatch):
        monkeypatch.setenv(ENV_CLOCK, "counter")
        monkeypatch.setenv(ENV_INJECT, "retrace=3")
        c = StepClock(flush_steps=2)
        for _ in range(6):
            c.mark()
        rows, s = c.finalize()
        assert s.retraces == 3
        # retrace rows land in whichever window saw them; the total is n
        assert sum(v for n, v in rows if n == "retraces") == 3.0

    def test_inject_straggle_scales_only_that_member(self, monkeypatch):
        monkeypatch.setenv(ENV_CLOCK, "counter")
        monkeypatch.setenv(ENV_INJECT, "straggle=1@4.0")
        fast = StepClock(flush_steps=10, member_index=0)
        slow = StepClock(flush_steps=10, member_index=1)
        solo = StepClock(flush_steps=10)  # member_index None: never straggled
        for c in (fast, slow, solo):
            for _ in range(3):
                c.mark()
        assert fast.finalize()[1].p95 == 1.0
        assert slow.finalize()[1].p95 == 4.0
        assert solo.finalize()[1].p95 == 1.0

    def test_malformed_inject_spec_is_ignored(self, monkeypatch):
        monkeypatch.setenv(ENV_CLOCK, "counter")
        monkeypatch.setenv(ENV_INJECT, "straggle=oops@x,retrace=nope,junk")
        c = StepClock(flush_steps=1, member_index=0)
        c.mark()
        _, s = c.finalize()
        assert s.retraces == 0 and s.p95 == 1.0

    def test_empty_clock_finalizes_to_zero_steps_and_no_rows(self):
        rows, s = StepClock().finalize()
        assert rows == [] and s.steps == 0

    def test_perf_logs_namespace_and_value_format(self):
        logs = perf_logs([("step_seconds_mean", 1.0)], timestamp=123.0)
        assert logs[0].metric_name == PERF_PREFIX + "step_seconds_mean"
        assert logs[0].value == "1.0" and logs[0].timestamp == 123.0
        assert perf_logs([]) == []

    def test_env_perf_logs_gated_and_windowed(self, monkeypatch):
        monkeypatch.delenv(ENV_STEP_STATS, raising=False)
        assert env_perf_logs("t-env-off", {"score": 1}) == []
        monkeypatch.setenv(ENV_STEP_STATS, "1")
        monkeypatch.setenv(ENV_CLOCK, "counter")
        monkeypatch.setenv(ENV_FLUSH_STEPS, "2")
        trial = f"t-env-{os.getpid()}-{time.time()}"
        assert env_perf_logs(trial, {"score": 1}) == []  # window not full yet
        logs = env_perf_logs(trial, {"score": 2})
        assert [l.metric_name for l in logs] == [
            PERF_PREFIX + "step_seconds_mean",
            PERF_PREFIX + "step_seconds_p95",
            PERF_PREFIX + "steps_per_second",
        ]


# -- MFU accounting -----------------------------------------------------------


class TestMfu:
    def test_peak_flops_table_substring_match(self):
        from katib_tpu.analysis.costmodel import peak_flops_for

        assert peak_flops_for("TPU v4") == 275e12
        assert peak_flops_for("TPU v5e") == 197e12
        assert peak_flops_for("TPU v5p") == 459e12
        assert peak_flops_for("NVIDIA H100 80GB HBM3") == 989e12
        assert peak_flops_for("cpu") is None  # no made-up peak: a CPU run has no MFU
        assert peak_flops_for("quantum-annealer") is None
        assert peak_flops_for(None) is None

    def test_peak_flops_agree_with_the_benchmarks_table(self, monkeypatch):
        """Two tables of peaks remain, the package's and the benchmark's:
        every device kind the benchmark knows reads the same in both."""
        from katib_tpu.analysis.costmodel import ENV_PEAK_FLOPS, peak_flops_for

        monkeypatch.delenv(ENV_PEAK_FLOPS, raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(repo, "benchmarks", "peaks.json")) as f:
            peaks = {k: v for k, v in json.load(f).items() if k != "source"}
        assert "TPU v5 lite" in peaks
        for kind, row in peaks.items():
            assert peak_flops_for(kind) == row["bf16_flops_per_s"], kind

    def test_peak_flops_env_override_wins(self, monkeypatch):
        from katib_tpu.analysis.costmodel import ENV_PEAK_FLOPS, peak_flops_for

        monkeypatch.setenv(ENV_PEAK_FLOPS, "5e12")
        assert peak_flops_for("TPU v4") == 5e12
        assert peak_flops_for("unknown") == 5e12

    def test_mfu_ratio(self):
        from katib_tpu.analysis.costmodel import mfu

        class Cost:
            flops = 100e12

        # 100 TFLOP step in 1s on 1 device with 275 TFLOP/s peak
        assert mfu(Cost(), 1.0, 1, device_kind="TPU v4") == pytest.approx(
            100e12 / 275e12
        )
        # explicit peak beats the table
        assert mfu(Cost(), 1.0, 2, peak=100e12) == pytest.approx(0.5)

    def test_mfu_none_on_missing_inputs(self):
        from katib_tpu.analysis.costmodel import mfu

        class Cost:
            flops = 100e12

        class NoFlops:
            flops = 0.0

        assert mfu(None, 1.0, 1, peak=1e12) is None
        assert mfu(Cost(), 0.0, 1, peak=1e12) is None
        assert mfu(Cost(), 1.0, 1, device_kind="unknown") is None
        assert mfu(NoFlops(), 1.0, 1, peak=1e12) is None


# -- reserved namespace -------------------------------------------------------


class TestReservedNamespace:
    def test_objective_under_perf_namespace_rejected(self):
        def fn(a, ctx):
            ctx.report(score=1.0)

        spec = _spec("bad-obj", fn)
        spec.objective.objective_metric_name = PERF_PREFIX + "steps_per_second"
        with pytest.raises(ValidationError, match="reserved"):
            validate_experiment(spec)

    def test_additional_metric_under_perf_namespace_rejected(self):
        def fn(a, ctx):
            ctx.report(score=1.0)

        spec = _spec("bad-extra", fn)
        spec.objective.additional_metric_names = [PERF_PREFIX + "stint_mfu"]
        with pytest.raises(ValidationError, match="reserved"):
            validate_experiment(spec)

    def test_fold_chokepoint_ignores_perf_rows(self):
        """``folded`` reads only the requested metric names — the single
        chokepoint that keeps perf rows out of objective folding, warm-start
        history points and BOHB rung models (all three consume folded
        observations by objective name)."""
        store = InMemoryObservationStore()
        store.report_observation_log("t", [
            MetricLog(timestamp=1.0, metric_name="score", value="0.5"),
            MetricLog(timestamp=1.0, metric_name=PERF_PREFIX + "step_seconds_mean",
                      value="1.0"),
            MetricLog(timestamp=2.0, metric_name="score", value="0.7"),
            MetricLog(timestamp=2.0, metric_name=PERF_PREFIX + "stint_mfu",
                      value="0.4"),
        ])
        obs = store.folded("t", ["score"])
        assert [m.name for m in obs.metrics] == ["score"]
        assert obs.metrics[0].latest == "0.7"


# -- detectors (controller plane) ---------------------------------------------


class _Exp:
    """Minimal experiment stand-in: the plane only reads .name/.spec."""

    def __init__(self, name):
        self.name = name
        self.spec = None


class TestDetectors:
    def _plane(self, **kw):
        events = EventRecorder()
        metrics = MetricsRegistry()
        return StepStatsPlane(metrics=metrics, events=events, **kw), events, metrics

    def _stint(self, n_steps, monkeypatch, factor=None, retraces=0):
        monkeypatch.setenv(ENV_CLOCK, "counter")
        if factor is not None:
            monkeypatch.setenv(ENV_INJECT, f"straggle=0@{factor}")
        else:
            monkeypatch.delenv(ENV_INJECT, raising=False)
        c = StepClock(flush_steps=1000, member_index=0 if factor else None)
        for _ in range(n_steps):
            c.mark()
        for _ in range(retraces + 1 if retraces else 0):
            c.note_compile()
        return c

    def test_retrace_storm_fires_above_threshold_only(self, monkeypatch):
        plane, events, metrics = self._plane(retrace_storm_threshold=3)
        store = InMemoryObservationStore()
        plane.finalize_stint(_Exp("e"), "t1",
                             self._stint(5, monkeypatch, retraces=3), store)
        assert not [e for e in events.list("e") if e.reason == "RetraceStorm"]
        plane.finalize_stint(_Exp("e"), "t2",
                             self._stint(5, monkeypatch, retraces=4), store)
        storms = [e for e in events.list("e") if e.reason == "RetraceStorm"]
        assert len(storms) == 1 and storms[0].event_type == "Warning"
        assert "t2" in storms[0].name
        rendered = metrics.render()
        assert 'katib_trial_retraces_total{experiment="e"} 7.0' in rendered

    def test_regression_detected_against_prior_stint_baseline(self, monkeypatch):
        plane, events, _ = self._plane(regression_ratio=1.5)
        store = InMemoryObservationStore()
        # stint 1: 1.0s steps — becomes the persisted baseline
        plane.finalize_stint(_Exp("e"), "t", self._stint(4, monkeypatch), store)
        assert not events.list("e")
        # stint 2 (resume/promotion): 4x slower than the baseline
        plane.finalize_stint(
            _Exp("e"), "t", self._stint(4, monkeypatch, factor=4.0), store
        )
        regs = [e for e in events.list("e") if e.reason == "StepTimeRegression"]
        assert len(regs) == 1 and "baseline 1.0000s" in regs[0].message

    def test_no_regression_when_resumed_stint_is_comparable(self, monkeypatch):
        plane, events, _ = self._plane(regression_ratio=1.5)
        store = InMemoryObservationStore()
        plane.finalize_stint(_Exp("e"), "t", self._stint(4, monkeypatch), store)
        plane.finalize_stint(_Exp("e"), "t", self._stint(4, monkeypatch), store)
        assert not [e for e in events.list("e")
                    if e.reason == "StepTimeRegression"]

    def test_regression_baseline_is_first_stint_not_last(self, monkeypatch):
        """Three stints at 1x, 1.2x-ish (still 1x under counter), then 4x:
        the FIRST persisted p50 stays the reference."""
        plane, events, _ = self._plane(regression_ratio=1.5)
        store = InMemoryObservationStore()
        for _ in range(2):
            plane.finalize_stint(_Exp("e"), "t", self._stint(3, monkeypatch), store)
        plane.finalize_stint(
            _Exp("e"), "t", self._stint(3, monkeypatch, factor=4.0), store
        )
        regs = [e for e in events.list("e") if e.reason == "StepTimeRegression"]
        assert len(regs) == 1

    def test_requeued_stint_writes_no_rows_and_no_baseline(self, monkeypatch):
        plane, events, _ = self._plane(regression_ratio=1.5)
        store = InMemoryObservationStore()
        plane.finalize_stint(
            _Exp("e"), "t", self._stint(4, monkeypatch), store, write_rows=False
        )
        assert store.get_observation_log("t") == []
        # a later slow stint has no baseline to regress against
        plane.finalize_stint(
            _Exp("e"), "t", self._stint(4, monkeypatch, factor=4.0), store
        )
        assert not [e for e in events.list("e")
                    if e.reason == "StepTimeRegression"]

    def test_gang_straggler_exactly_one_member_flagged(self, monkeypatch):
        monkeypatch.setenv(ENV_CLOCK, "counter")
        monkeypatch.setenv(ENV_INJECT, "straggle=2@8.0")
        plane, events, _ = self._plane(straggler_ratio=2.0)
        store = InMemoryObservationStore()
        clocks = [StepClock(flush_steps=1000, member_index=i) for i in range(4)]
        for c in clocks:
            for _ in range(4):
                c.mark()
        plane.finalize_pack(
            _Exp("e"), [f"m{i}" for i in range(4)], clocks, store, n_devices=8
        )
        stragglers = [e for e in events.list("e") if e.reason == "GangStraggler"]
        assert len(stragglers) == 1 and stragglers[0].name == "m2"
        # every member still wrote its stint rows
        for i in range(4):
            assert any(
                l.metric_name == PERF_PREFIX + "stint_step_seconds_p50"
                for l in store.get_observation_log(f"m{i}")
            )

    def test_gang_straggler_needs_two_measured_members(self, monkeypatch):
        monkeypatch.setenv(ENV_CLOCK, "counter")
        monkeypatch.setenv(ENV_INJECT, "straggle=0@8.0")
        plane, events, _ = self._plane(straggler_ratio=2.0)
        store = InMemoryObservationStore()
        c = StepClock(flush_steps=1000, member_index=0)
        for _ in range(4):
            c.mark()
        plane.finalize_pack(_Exp("e"), ["m0"], [c], store)
        assert not events.list("e")

    def test_rollup_gauges_and_forget(self, monkeypatch):
        plane, _, metrics = self._plane()
        store = InMemoryObservationStore()
        plane.finalize_stint(_Exp("e"), "t", self._stint(4, monkeypatch), store)
        plane.charge_device_seconds("e", 10.0)
        plane.note_objective("e", 0.5, maximize=True)
        plane.note_objective("e", 0.8, maximize=True)
        plane.note_objective("e", 0.2, maximize=True)
        rendered = metrics.render()
        assert 'katib_step_seconds{experiment="e",quantile="p50"} 1.0' in rendered
        assert 'katib_step_seconds{experiment="e",quantile="p95"} 1.0' in rendered
        assert 'katib_trial_throughput{experiment="e"} 1.0' in rendered
        assert ('katib_objective_per_device_second{experiment="e"} 0.08'
                in rendered)
        plane.forget_experiment("e")
        assert 'experiment="e"' not in metrics.render()


# -- summaries ----------------------------------------------------------------


def test_summarize_perf_rows():
    logs = [
        MetricLog(1.0, "score", "0.5"),
        MetricLog(1.0, PERF_PREFIX + "step_seconds_mean", "1.0"),
        MetricLog(1.0, PERF_PREFIX + "step_seconds_p95", "1.5"),
        MetricLog(1.0, PERF_PREFIX + "steps_per_second", "1.0"),
        MetricLog(2.0, PERF_PREFIX + "retraces", "2.0"),
        MetricLog(2.0, PERF_PREFIX + "step_seconds_mean", "1.0"),
        MetricLog(3.0, PERF_PREFIX + "stint_step_seconds_p50", "1.0"),
        MetricLog(3.0, PERF_PREFIX + "stint_step_seconds_p95", "1.5"),
        MetricLog(3.0, PERF_PREFIX + "stint_mfu", "0.41"),
    ]
    s = summarize_perf_rows(logs)
    assert s == {
        "windows": 2,
        "stints": 1,
        "stepSecondsP50": 1.0,
        "stepSecondsP95": 1.5,
        "stepsPerSecond": 1.0,
        "examplesPerSecond": None,
        "mfu": 0.41,
        "retraces": 2,
    }
    assert summarize_perf_rows([MetricLog(1.0, "score", "0.5")]) is None


def test_fleet_metrics_summary_folds_perf_families():
    from katib_tpu.service.httpapi import _metrics_summary

    text = "\n".join([
        "# HELP katib_step_seconds x",
        'katib_step_seconds{experiment="e1",quantile="p50"} 0.5',
        'katib_step_seconds{experiment="e1",quantile="p95"} 0.9',
        'katib_trial_throughput{experiment="e1"} 12.0',
        'katib_trial_mfu_ratio{experiment="e1"} 0.43',
        'katib_trial_retraces_total{experiment="e1"} 3.0',
        'katib_objective_per_device_second{experiment="e1"} 0.002',
        "katib_rpc_requests_total 7",
    ])
    m = _metrics_summary(text)
    assert m["rpcRequests"] == 7.0
    assert m["perf"]["e1"] == {
        "p50": 0.5, "p95": 0.9, "throughput": 12.0, "mfu": 0.43,
        "retraces": 3.0, "objectivePerDeviceSecond": 0.002,
    }
    # knob off: no perf families -> no perf key at all (fleet JSON stays
    # byte-identical to the pre-perf plane)
    assert "perf" not in _metrics_summary("katib_rpc_requests_total 7\n")


# -- end-to-end: knob gating + identity ---------------------------------------


def _seeded_run(step_stats, n_reports=6, warm_start=False):
    def trial_fn(assignments, ctx):
        x = float(assignments["x"])
        for step in range(1, n_reports + 1):
            ctx.report(score=x * step, examples=8)

    cfg = KatibConfig()
    cfg.runtime.step_stats = step_stats
    cfg.runtime.step_stats_flush_steps = 2
    cfg.runtime.tracing = True
    if warm_start:
        cfg.runtime.warm_start = True
    ctrl = ExperimentController(
        root_dir=None, devices=list(range(2)), persist=False, config=cfg
    )
    try:
        ctrl.create_experiment(_spec("seeded", trial_fn, n_trials=3))
        exp = ctrl.run("seeded", timeout=120)
        assert exp.status.trials_succeeded == 3
        rows, folded, spans = {}, {}, {}
        for t in ctrl.state.list_trials("seeded"):
            x = t.assignments_dict()["x"]
            rows[x] = [
                (l.metric_name, l.value)
                for l in ctrl.obs_store.get_observation_log(t.name)
            ]
            folded[x] = [
                (m.name, m.latest) for m in (t.observation.metrics or [])
            ] if t.observation else []
            trace = ctrl.tracer.trial_trace("seeded", t.name)
            spans[x] = sorted(s["name"] for s in (trace or {}).get("spans", []))
        return rows, folded, spans, ctrl.metrics.render()
    finally:
        ctrl.close()


class TestKnobGating:
    def test_off_is_default_and_byte_identical(self, monkeypatch):
        monkeypatch.delenv(ENV_STEP_STATS, raising=False)
        monkeypatch.setenv(ENV_CLOCK, "counter")
        assert KatibConfig().runtime.step_stats is False
        off_rows, off_folded, off_spans, off_render = _seeded_run(False)
        on_rows, on_folded, on_spans, on_render = _seeded_run(True)
        # knob off: zero perf rows, no step families on /metrics
        assert all(
            not n.startswith(PERF_PREFIX) for r in off_rows.values() for n, _ in r
        )
        for family in ("katib_step_seconds", "katib_trial_throughput",
                       "katib_trial_mfu_ratio", "katib_trial_retraces_total",
                       "katib_objective_per_device_second"):
            assert family not in off_render
        assert "katib_step_seconds" in on_render
        # the plane adds no spans: span sets identical on vs off
        assert on_spans == off_spans
        # non-perf observation rows are bit-identical on vs off (the clock
        # observes, never consumes)
        on_nonperf = {
            x: [(n, v) for n, v in r if not n.startswith(PERF_PREFIX)]
            for x, r in on_rows.items()
        }
        assert on_nonperf == off_rows
        # folded observations identical: perf rows never fold
        assert on_folded == off_folded
        # and the on-run actually measured: windows + stint rows per trial
        for x, r in on_rows.items():
            names = [n for n, _ in r if n.startswith(PERF_PREFIX)]
            assert PERF_PREFIX + "step_seconds_mean" in names
            assert PERF_PREFIX + "stint_step_seconds_p50" in names

    def test_warm_start_history_identical_on_vs_off(self, monkeypatch):
        """Transfer-HPO history points are folded objectives — a knob-on run
        must persist exactly the history a knob-off run does."""
        monkeypatch.setenv(ENV_CLOCK, "counter")
        from katib_tpu.controller.suggestion import warm_start_signature

        def run(step_stats):
            def trial_fn(assignments, ctx):
                x = float(assignments["x"])
                for step in range(1, 4):
                    ctx.report(score=x * step)

            cfg = KatibConfig()
            cfg.runtime.step_stats = step_stats
            cfg.runtime.warm_start = True
            ctrl = ExperimentController(
                root_dir=None, devices=list(range(2)), persist=False, config=cfg
            )
            try:
                spec = _spec("warm", trial_fn, n_trials=3)
                ctrl.create_experiment(spec)
                ctrl.run("warm", timeout=120)
                sig = warm_start_signature(spec)
                return sig, ctrl.obs_store.matching_history(sig)
            finally:
                ctrl.close()

        sig_off, hist_off = run(False)
        sig_on, hist_on = run(True)
        assert sig_on == sig_off
        assert hist_on == hist_off
        assert hist_off, "seeded run produced no warm-start history"


class TestPackedE2E:
    def test_each_member_gets_its_own_perf_series(self, monkeypatch):
        monkeypatch.setenv(ENV_CLOCK, "counter")
        monkeypatch.delenv(ENV_INJECT, raising=False)
        from katib_tpu.runtime.packed import population_of, report_population

        def pack_fn(assignments, ctx=None):
            lr = population_of(assignments)["x"]
            for step in range(4):
                report_population(ctx, score=lr * (step + 1), examples=4)

        pack_fn.supports_packing = True
        cfg = KatibConfig()
        cfg.runtime.step_stats = True
        cfg.runtime.step_stats_flush_steps = 2
        ctrl = ExperimentController(
            root_dir=None, devices=list(range(8)), persist=False, config=cfg
        )
        try:
            ctrl.create_experiment(
                _spec("pk", pack_fn, n_trials=4, parallel=4, pack_size=4)
            )
            exp = ctrl.run("pk", timeout=120)
            assert exp.status.trials_succeeded == 4
            rows = _perf_rows(ctrl, "pk")
            assert len(rows) == 4
            for r in rows.values():
                names = [n for n, _ in r]
                assert PERF_PREFIX + "step_seconds_mean" in names
                assert PERF_PREFIX + "stint_step_seconds_p50" in names
                # counter clock: packed members record 1.0s steps exactly
                assert (PERF_PREFIX + "stint_step_seconds_p50", "1.0") in r
        finally:
            ctrl.close()


# -- CLI ----------------------------------------------------------------------


class TestPerfCli:
    def _persisted_run(self, tmp_path, step_stats=True):
        def trial_fn(assignments, ctx):
            x = float(assignments["x"])
            for step in range(1, 5):
                ctx.report(score=x * step, examples=8)

        cfg = KatibConfig()
        cfg.runtime.step_stats = step_stats
        cfg.runtime.step_stats_flush_steps = 2
        ctrl = ExperimentController(
            root_dir=str(tmp_path), devices=list(range(2)), config=cfg
        )
        try:
            ctrl.create_experiment(_spec("cli-exp", trial_fn, n_trials=2))
            exp = ctrl.run("cli-exp", timeout=120)
            assert exp.status.trials_succeeded == 2
        finally:
            ctrl.close()

    def test_cmd_perf_table_and_json(self, tmp_path, monkeypatch, capsys):
        monkeypatch.setenv(ENV_CLOCK, "counter")
        monkeypatch.delenv(ENV_INJECT, raising=False)
        from katib_tpu.cli import main

        self._persisted_run(tmp_path)
        assert main(["--root", str(tmp_path), "perf", "cli-exp"]) == 0
        out = capsys.readouterr().out
        assert "TRIAL" in out and "STEP-P50" in out and "RETRACES" in out
        assert "1.0000" in out  # counter clock p50
        assert main(
            ["--root", str(tmp_path), "perf", "cli-exp", "--format", "json"]
        ) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["experiment"] == "cli-exp"
        assert len(doc["trials"]) == 2
        for t in doc["trials"]:
            assert t["status"] == "Succeeded"
            assert t["stepSecondsP50"] == 1.0
            assert t["stints"] == 1 and t["windows"] >= 1

    def test_cmd_perf_without_rows_explains(self, tmp_path, capsys):
        from katib_tpu.cli import main

        self._persisted_run(tmp_path, step_stats=False)
        assert main(["--root", str(tmp_path), "perf", "cli-exp"]) == 0
        out = capsys.readouterr().out
        assert "KATIB_TPU_STEP_STATS" in out

    def test_cmd_perf_unknown_experiment(self, tmp_path, capsys):
        from katib_tpu.cli import main

        self._persisted_run(tmp_path, step_stats=False)
        assert main(["--root", str(tmp_path), "perf", "nope"]) == 1
        assert "not found" in capsys.readouterr().err


class TestProfileLinkage:
    def test_profile_dir_stamped_on_trial_root_span(self, tmp_path, capsys):
        """Satellite: a retained trial that captured an xplane dump gets the
        dump path stamped on its root span at finalize, and the experiment
        trace table shows it in the PROFILE column."""
        import jax.numpy as jnp

        def trial_fn(assignments, ctx):
            with ctx.profile():
                x = jnp.ones((4, 4))
                (x @ x).block_until_ready()
            ctx.report(score=1.0)

        cfg = KatibConfig()
        cfg.runtime.tracing = True
        ctrl = ExperimentController(
            root_dir=str(tmp_path), devices=list(range(2)), config=cfg
        )
        try:
            ctrl.create_experiment(
                _spec("prof", trial_fn, n_trials=1, retain=True)
            )
            ctrl.run("prof", timeout=120)
            t = ctrl.state.list_trials("prof")[0]
        finally:
            ctrl.close()
        from katib_tpu.tracing import experiment_traces

        traces = experiment_traces(str(tmp_path), "prof")
        assert traces
        roots = [s for s in traces[0]["spans"] if s.get("parentId") is None]
        assert roots, "no root span in persisted trace"
        profile_dir = roots[0]["attrs"].get("profileDir")
        assert profile_dir and profile_dir.endswith(os.path.join(t.name, "profile"))
        assert os.path.isdir(profile_dir)
        from katib_tpu.cli import main

        assert main(["--root", str(tmp_path), "trace", "prof"]) == 0
        out = capsys.readouterr().out
        assert "PROFILE" in out and "profile" in out


# -- SIGKILL failover continuity (PR 15 harness) ------------------------------


FO_TRIAL_MODULE = """\
import time

def run_trial(assignments, ctx):
    x = float(assignments["x"])
    for epoch in range(1, {epochs} + 1):
        time.sleep({dwell})
        ctx.report(score=x * (1.0 - 0.8 ** epoch), epoch=epoch, examples=8)
"""


def _fo_spec(name, n_trials=2, parallel=2):
    step = 0.9 / max(n_trials - 1, 1)
    return {
        "name": name,
        "parameters": [{
            "name": "x", "parameterType": "double",
            "feasibleSpace": {"min": "0.1", "max": "1.0", "step": repr(step)},
        }],
        "objective": {"type": "maximize", "objectiveMetricName": "score"},
        "algorithm": {"algorithmName": "grid"},
        "trialTemplate": {
            "entryPoint": "fo_trial:run_trial",
            "trialParameters": [{"name": "x", "reference": "x"}],
        },
        "maxTrialCount": n_trials,
        "parallelTrialCount": parallel,
        "resumePolicy": "FromVolume",
    }


def _is_done(status_doc):
    if not status_doc:
        return False
    return any(
        c.get("type") in ("Succeeded", "Failed") and c.get("status")
        for c in status_doc.get("status", {}).get("conditions", [])
    )


class TestFailoverPerfContinuity:
    def test_failed_over_trial_perf_series_bit_identical(self):
        """A replica SIGKILLed mid-sweep: the experiment completes on the
        survivor and every trial's perf series — produced by the env-bound
        clock in the trial subprocess under the deterministic counter clock
        — is bit-identical to a fault-free single-replica run."""
        from katib_tpu.client.katib_client import ReplicaRouter
        from katib_tpu.db.state import ExperimentStateStore
        from katib_tpu.db.store import SqliteObservationStore

        epochs = 4
        name = "fo-perf"

        def drive(root, replicas, kill_after_place):
            with open(os.path.join(root, "fo_trial.py"), "w") as f:
                f.write(FO_TRIAL_MODULE.format(epochs=epochs, dwell=0.25))
            env = dict(os.environ)
            env.update({
                "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": (
                    REPO + os.pathsep + root + os.pathsep
                    + env.get("PYTHONPATH", "")
                ).rstrip(os.pathsep),
                "KATIB_TPU_REPLICAS": str(replicas),
                "KATIB_TPU_REPLICA_CAPACITY": "8",
                "KATIB_TPU_PLACEMENT_LEASE_SECONDS": "5.0",
                "KATIB_TPU_TELEMETRY": "0",
                "KATIB_TPU_COMPILE_SERVICE": "0",
                "KATIB_TPU_TRACING": "0",
                "KATIB_TPU_OBSLOG_BUFFERED": "0",
                ENV_STEP_STATS: "1",
                ENV_CLOCK: "counter",
                ENV_FLUSH_STEPS: "1",
            })
            env.pop("KATIB_TPU_CHAOS", None)
            env.pop(ENV_INJECT, None)
            procs, logs = [], []
            try:
                for i in range(replicas):
                    out = open(os.path.join(root, f"r{i}.log"), "w")
                    procs.append(subprocess.Popen(
                        [sys.executable, "-m", "katib_tpu.controller.replica",
                         "--root", root, "--replica-id", f"r{i}",
                         "--devices", "2"],
                        env=env, stdout=out, stderr=out, text=True,
                    ))
                    logs.append(out)
                router = ReplicaRouter(root)
                deadline = time.time() + 120
                while len(router.live_replicas()) < replicas:
                    assert time.time() < deadline, "replicas never joined"
                    time.sleep(0.2)
                placed = router.create_experiment(_fo_spec(name))["replica"]
                if kill_after_place:
                    time.sleep(1.0)
                    victim = int(placed[1:])
                    procs[victim].send_signal(signal.SIGKILL)
                    procs[victim].wait()
                while not _is_done(router.experiment_status(name)):
                    assert time.time() < deadline, "experiment never completed"
                    time.sleep(0.3)
            finally:
                for p in procs:
                    if p.poll() is None:
                        p.terminate()
                for p in procs:
                    if p.poll() is None:
                        try:
                            p.wait(timeout=10)
                        except subprocess.TimeoutExpired:
                            p.kill()
                for f in logs:
                    f.close()
            state = ExperimentStateStore(os.path.join(root, "state"))
            store = SqliteObservationStore(
                os.path.join(root, "observations.db")
            )
            series = {}
            try:
                state.load(name)
                for t in state.list_trials(name):
                    key = t.assignments_dict()["x"]
                    series[key] = [
                        (l.metric_name, l.value)
                        for l in store.get_observation_log(t.name)
                        if l.metric_name.startswith(PERF_PREFIX)
                    ]
            finally:
                store.close()
            return series

        ref_root = tempfile.mkdtemp(prefix="sp-ref-")
        chaos_root = tempfile.mkdtemp(prefix="sp-chaos-")
        try:
            ref = drive(ref_root, replicas=1, kill_after_place=False)
            assert ref and all(rows for rows in ref.values()), (
                f"fault-free run produced no perf series: {ref}"
            )
            # counter clock + flush=1: each epoch's report is one complete
            # window — a continuous series with no gaps
            for rows in ref.values():
                means = [v for n, v in rows
                         if n == PERF_PREFIX + "step_seconds_mean"]
                assert means == ["1.0"] * epochs
            chaos = drive(chaos_root, replicas=2, kill_after_place=True)
            assert chaos == ref, (
                "failed-over perf series is not bit-identical to the "
                "fault-free run"
            )
        finally:
            import shutil

            shutil.rmtree(ref_root, ignore_errors=True)
            shutil.rmtree(chaos_root, ignore_errors=True)
