"""bench.py orchestration: one total deadline governs probe → TPU child →
CPU child → sentinel, and a killed child's checkpointed stages are salvaged.

Round-3 regression: the children's summed worst-case budgets exceeded the
driver's timeout, so a wedged backend produced rc=124 and NO output
(BENCH_r03.json parsed: null). These tests pin the new invariant — bench.py
always prints exactly one parseable JSON line inside BENCH_TOTAL_BUDGET —
without running the heavyweight measurement stages (children are stubbed)."""

import importlib.util
import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def bench(monkeypatch):
    from tests.conftest import load_bench_module

    mod = load_bench_module()
    # isolate from the ambient env: no caps, default budgets
    for var in (
        "BENCH_TOTAL_BUDGET", "BENCH_TPU_TIMEOUT", "BENCH_CPU_TIMEOUT",
        "BENCH_FORCE_CPU", "BENCH_TPU_ATTEMPTS", "BENCH_PROBE_TIMEOUT",
        "BENCH_CPU_RESERVE", "BENCH_RESULT_FILE", "BENCH_CHILD_DEADLINE",
        "BENCH_NOMINAL_DARTS_STEP_MS", "BENCH_NOMINAL_DARTS_STEP_MS_CPU",
        "BENCH_NOMINAL_DARTS_STEP_MS_TPU", "BENCH_STEPS",
        "BENCH_PROBE_MAX_RT_MS", "BENCH_PROBE_DEGRADED_RT_MS",
        "BENCH_PROBE_MAX_ATTEMPTS",
    ):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("BENCH_RETRY_SLEEP", "0")  # stubbed children: no backoff
    # stubbed probes return instantly; without these the retry loop would
    # spend real wall-clock sleeping between attempts
    monkeypatch.setenv("BENCH_PROBE_RETRY_SLEEP", "0")
    monkeypatch.setenv("BENCH_PROBE_MAX_ATTEMPTS", "3")
    return mod


def _run_main(bench, capsys):
    bench.main()
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1, out
    return json.loads(out[-1])


def test_wedged_probe_skips_to_cpu(bench, monkeypatch, capsys):
    """A wedged backend (probe failure) must hand the CPU child the whole
    remaining envelope and attach the probe diagnostic to the result."""
    calls = []
    monkeypatch.setattr(bench, "_probe_tpu", lambda t: ("dead", "probe timed out after 42s", None))

    def fake_child(platform, timeout_s, extra_env=None):
        calls.append((platform, timeout_s))
        assert platform == "cpu"
        return {"metric": "m", "value": 1.0, "extras": {}}, None

    monkeypatch.setattr(bench, "_run_child", fake_child)
    result = _run_main(bench, capsys)
    assert calls and calls[0][0] == "cpu"
    # CPU child got nearly the whole budget (1140 default - 20 margin)
    assert calls[0][1] > 1000
    assert "probe" in result["extras"]["tpu_init_errors"][0]


def test_healthy_probe_runs_tpu_child(bench, monkeypatch, capsys):
    monkeypatch.setattr(bench, "_probe_tpu", lambda t: ("healthy", "rt 2.1ms on TPU v5 lite", 2.1))

    seen = {}

    def fake_child(platform, timeout_s, extra_env=None):
        assert platform == "tpu"
        # TPU child budget = total - probe - cpu_reserve - margin
        assert 500 < timeout_s < 1140
        seen["extra"] = extra_env
        return {"metric": "m", "value": 1.0, "extras": {}}, None

    monkeypatch.setattr(bench, "_run_child", fake_child)
    result = _run_main(bench, capsys)
    assert result["extras"]["probe"].startswith("rt 2.1ms")
    # healthy backend: no timed-loop override is injected into the child
    assert not seen["extra"]


def test_tpu_result_missing_darts_mfu_carries_freshest_capture(
    bench, monkeypatch, capsys
):
    """A TPU run squeezed/killed before the reference-scale darts_mfu stage
    still ships that number via the freshest watcher capture, labeled; a
    run that measured it itself does not get the redundant attachment."""
    monkeypatch.setattr(bench, "_probe_tpu", lambda t: ("healthy", "rt 2ms", 2.0))
    capture = {
        "file": "examples/records/bench_tpu_20260801.json",
        "darts_mfu_reference_scale": 0.31,
        "provenance": "builder watcher capture",
    }
    monkeypatch.setattr(bench, "_freshest_tpu_capture", lambda: dict(capture))

    child_result = {"metric": "m", "value": 1.0, "extras": {}}
    monkeypatch.setattr(
        bench, "_run_child",
        lambda p, t, extra_env=None: (json.loads(json.dumps(child_result)), None),
    )
    result = _run_main(bench, capsys)
    assert result["extras"]["freshest_tpu_capture"]["darts_mfu_reference_scale"] == 0.31

    child_result["extras"] = {"darts_mfu": {"mfu": 0.28, "step_ms": 50.0}}
    result = _run_main(bench, capsys)
    assert "freshest_tpu_capture" not in result["extras"]


def test_degraded_probe_still_benches_tpu_with_longer_loops(
    bench, monkeypatch, capsys
):
    """rt between the healthy threshold and the ceiling: run the TPU child
    anyway (the chained loops subtract the round-trip, so a slow backend adds
    noise, not bias) but lengthen ITS timed loops to amortize it — the CPU
    fallback child must not inherit the override (no accelerator there)."""
    monkeypatch.setattr(
        bench,
        "_probe_tpu",
        lambda t: ("degraded", "rt 98.1ms on TPU v5 lite (> 40ms ...)", 98.1),
    )
    seen = []

    def fake_child(platform, timeout_s, extra_env=None):
        seen.append((platform, (extra_env or {}).get("BENCH_STEPS")))
        if platform == "tpu":
            return None, "tpu child rc=1: boom"
        return {"metric": "m", "value": 1.0, "extras": {}}, None

    monkeypatch.setattr(bench, "_run_child", fake_child)
    monkeypatch.setenv("BENCH_TPU_ATTEMPTS", "1")
    result = _run_main(bench, capsys)
    assert seen[0] == ("tpu", str(int(98.1 * 0.9)))
    assert seen[-1] == ("cpu", None)
    assert result["extras"]["tpu_init_errors"] == ["tpu child rc=1: boom"]


def test_degraded_probe_respects_pinned_steps(bench, monkeypatch, capsys):
    monkeypatch.setenv("BENCH_STEPS", "12")
    monkeypatch.setattr(
        bench, "_probe_tpu", lambda t: ("degraded", "rt 120ms", 120.0)
    )
    seen = {}

    def fake_child(platform, timeout_s, extra_env=None):
        seen["extra"] = extra_env
        return {"metric": "m", "value": 1.0, "extras": {}}, None

    monkeypatch.setattr(bench, "_run_child", fake_child)
    _run_main(bench, capsys)
    assert not seen["extra"]  # pinned BENCH_STEPS wins; no override injected


def test_probe_tpu_classifies_roundtrip(bench, monkeypatch):
    """Real _probe_tpu over a stubbed subprocess: healthy / degraded / dead
    by round-trip alone."""
    import json as _json

    class FakeProc:
        returncode = 0

        def __init__(self, rt):
            self.stdout = _json.dumps({"rt_ms": rt, "device_kind": "TPU v5 lite"})
            self.stderr = ""

    for rt, expected in ((5.0, "healthy"), (98.0, "degraded"), (400.0, "dead")):
        monkeypatch.setattr(
            bench.subprocess, "run", lambda *a, _rt=rt, **k: FakeProc(_rt)
        )
        verdict, diag, got_rt = bench._probe_tpu(30.0)
        assert verdict == expected, (rt, verdict, diag)
        if expected == "dead":
            assert got_rt is None
        else:
            assert got_rt == rt


def test_tpu_timeout_salvage_reports_partial(bench, monkeypatch, capsys):
    """A TPU child killed mid-run still reports its checkpointed stages."""
    monkeypatch.setattr(bench, "_probe_tpu", lambda t: ("healthy", "rt 2ms", 2.0))

    def fake_child(platform, timeout_s, extra_env=None):
        if platform == "tpu":
            return (
                {"metric": "m", "value": 9.0,
                 "extras": {"partial": "tpu child timed out after 700s",
                            "mfu_small": 0.5}},
                "tpu child timed out after 700s",
            )
        raise AssertionError("CPU fallback must not run when salvage succeeded")

    monkeypatch.setattr(bench, "_run_child", fake_child)
    result = _run_main(bench, capsys)
    assert result["value"] == 9.0
    assert "partial" in result["extras"]


def test_all_arms_fail_prints_sentinel(bench, monkeypatch, capsys):
    monkeypatch.setattr(bench, "_probe_tpu", lambda t: ("healthy", "rt 2ms", 2.0))
    monkeypatch.setattr(bench, "_run_child", lambda p, t, extra_env=None: (None, f"{p} child rc=1: boom"))
    result = _run_main(bench, capsys)
    assert result["value"] == -1.0
    assert any("boom" in e for e in result["extras"]["errors"])


def test_tiny_budget_prints_sentinel_fast(bench, monkeypatch, capsys):
    """The guarantee that zeroed round 3: even a budget too small for any
    child still yields one parseable line, quickly."""
    monkeypatch.setenv("BENCH_TOTAL_BUDGET", "5")
    t0 = time.time()
    result = _run_main(bench, capsys)
    assert time.time() - t0 < 10
    assert result["value"] == -1.0
    assert result["vs_baseline"] == 0.0


def test_tpu_fast_failure_retries_then_cpu(bench, monkeypatch, capsys):
    monkeypatch.setattr(bench, "_probe_tpu", lambda t: ("healthy", "rt 2ms", 2.0))
    calls = []

    def fake_child(platform, timeout_s, extra_env=None):
        calls.append(platform)
        if platform == "tpu":
            return None, "tpu child rc=1: init error"
        return {"metric": "m", "value": 2.0, "extras": {}}, None

    monkeypatch.setattr(bench, "_run_child", fake_child)
    result = _run_main(bench, capsys)
    assert calls == ["tpu", "tpu", "cpu"]  # fast failure retried once
    assert len(result["extras"]["tpu_init_errors"]) == 2


def test_tpu_timeout_does_not_retry(bench, monkeypatch, capsys):
    """A timed-out (wedged) TPU child must not be re-queued — the CPU
    fallback gets the remaining budget instead."""
    monkeypatch.setattr(bench, "_probe_tpu", lambda t: ("healthy", "rt 2ms", 2.0))
    calls = []

    def fake_child(platform, timeout_s, extra_env=None):
        calls.append(platform)
        if platform == "tpu":
            return None, "tpu child timed out after 700s"
        return {"metric": "m", "value": 2.0, "extras": {}}, None

    monkeypatch.setattr(bench, "_run_child", fake_child)
    _run_main(bench, capsys)
    assert calls == ["tpu", "cpu"]


def test_darts_mfu_oom_retries_once_with_remat(bench, monkeypatch):
    """HBM exhaustion on the plain reference-scale step triggers exactly one
    retry with remat_cells=1; a second failure reports the remat-specific
    memory note instead of recursing again."""
    import katib_tpu.models.darts_trainer as dt

    seen = []

    class FakeSearch:
        def __init__(self, primitives, num_layers, settings):
            seen.append(dict(settings))
            self.settings = settings

        def build(self, shape, steps):
            if self.settings.get("remat_cells") == "1":
                raise RuntimeError("RESOURCE_EXHAUSTED: still 2.1G over")
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory allocating")

    monkeypatch.setattr(dt, "DartsSearch", FakeSearch)
    monkeypatch.setenv("BENCH_CHILD_DEADLINE", str(time.time() + 3600))
    out = bench._bench_darts_mfu(None, __import__("numpy"))
    assert len(seen) == 2
    assert seen[0].get("remat_cells") is None
    assert seen[1].get("remat_cells") == "1"
    assert "error" in out and "even with remat_cells=1" in out["memory_note"]


def test_checkpoint_and_salvage_roundtrip(bench, tmp_path, monkeypatch):
    """_checkpoint_stage writes atomically; _salvage recovers it and tags
    the payload as partial."""
    rf = str(tmp_path / "result.json")
    monkeypatch.setenv("BENCH_RESULT_FILE", rf)
    payload = {"metric": "m", "value": 3.0, "extras": {"darts_step_ms": 2.0}}
    bench._checkpoint_stage(payload)
    got = bench._salvage(rf, "killed at stage lm")
    assert got["value"] == 3.0
    assert got["extras"]["partial"] == "killed at stage lm"
    assert bench._salvage(str(tmp_path / "missing.json"), "x") is None


def test_sentinel_via_real_subprocess():
    """End-to-end through the real CLI: an impossible budget still produces
    one JSON line on stdout with rc=0, well inside the budget."""
    env = dict(os.environ)
    env["BENCH_TOTAL_BUDGET"] = "5"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=30, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-500:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1
    parsed = json.loads(lines[0])
    assert parsed["metric"] == "darts_cifar10_e2e_steady_state_epoch"


def test_e2e_plan_contention_inflates_estimates(bench, monkeypatch):
    """Round-4 regression: fixed estimates calibrated on a quiet box fit 0
    trials when the box ran ~2.6x slow under three concurrent suites. The
    plan must divide the darts stage's measured step time by the nominal pin
    and inflate per-trial estimates by that factor."""
    monkeypatch.delenv("BENCH_NOMINAL_DARTS_STEP_MS", raising=False)
    # uncontended: 900s fits the learnable rung's cold compile (650s) but
    # only ONE trial there — distribution-first degrades to the warm rung
    # (>=3 accuracies beat a single bigger-model point)
    scale, n, contention = bench._e2e_plan(False, 900.0, {"step_ms": 1100.0}, 3)
    assert contention == 1.0
    assert scale["init_channels"] == 1 and n == 3
    # with room for 3 learnable trials (650 + 2*350), the bigger rung wins
    scale, n, contention = bench._e2e_plan(False, 1400.0, {"step_ms": 1100.0}, 3)
    assert scale["init_channels"] == 4 and n == 3
    # 2.6x contention: learnable first trial alone would cost 1690s of 620
    # — must degrade to the warm-cache headline rung, not time out at the
    # learnable scale
    scale, n, contention = bench._e2e_plan(False, 620.0, {"step_ms": 2860.0}, 3)
    assert contention == pytest.approx(2.6)
    assert scale["init_channels"] == 1 and scale["num_nodes"] == 1
    assert scale["schedule_horizon"] == bench.STEPS_PER_EPOCH
    assert n == 3  # warm rung fits all requested trials


def test_e2e_plan_faster_than_pin_keeps_margin(bench, monkeypatch):
    """A box faster than the nominal pin must NOT deflate the estimates
    (contention clamps at 1.0) — the margin absorbs run-to-run variance."""
    monkeypatch.delenv("BENCH_NOMINAL_DARTS_STEP_MS", raising=False)
    fast, n, contention = bench._e2e_plan(False, 1400.0, {"step_ms": 300.0}, 3)
    assert contention == 1.0
    # 1400 >= 650 + 2*350 at UN-deflated estimates: learnable rung, 3 trials
    assert fast["init_channels"] == 4 and n == 3


def test_e2e_plan_no_rung_fits(bench, monkeypatch):
    """When even the cheapest rung cannot fit one trial, the stage is
    skipped with a reason instead of burning the child's whole envelope."""
    monkeypatch.delenv("BENCH_NOMINAL_DARTS_STEP_MS", raising=False)
    assert bench._e2e_plan(False, 50.0, {"step_ms": 1200.0}, 3) is None
    # missing darts measurement degrades gracefully to contention=1; 400s
    # cannot fit the learnable cold compile but fits the warm rung
    scale, n, contention = bench._e2e_plan(False, 400.0, None, 3)
    assert contention == 1.0 and scale["init_channels"] == 1 and n == 3


def test_e2e_plan_per_backend_nominal_override(bench, monkeypatch):
    """One run can execute BOTH children under the same env: a TPU-side
    recalibration must not corrupt the CPU fallback's contention estimate."""
    monkeypatch.setenv("BENCH_NOMINAL_DARTS_STEP_MS_TPU", "25")
    monkeypatch.delenv("BENCH_NOMINAL_DARTS_STEP_MS", raising=False)
    _, _, contention = bench._e2e_plan(False, 900.0, {"step_ms": 1100.0}, 3)
    assert contention == 1.0  # CPU still uses the CPU pin, not 1100/25=44x
    monkeypatch.setenv("BENCH_NOMINAL_DARTS_STEP_MS", "600")
    _, _, contention = bench._e2e_plan(False, 9000.0, {"step_ms": 1200.0}, 3)
    assert contention == 2.0  # shared name is the fallback for CPU


def test_warm_rung_shares_compiled_step_with_darts_stage(bench):
    """The warm-cache rung only earns its cheap estimates if an e2e trial's
    DartsSearch resolves to the SAME compiled search step _bench_darts
    already built in this process: equal module config + schedule_horizon
    pinned to the stage's total_steps must be an lru hit, and a different
    horizon must miss."""
    from katib_tpu.models.darts_trainer import DartsSearch

    rung = bench._e2e_plan(False, 500.0, {"step_ms": 3120.0}, 3)[0]
    prims = rung["primitives"]
    stage = DartsSearch(
        primitives=prims, num_layers=3,
        settings={"num_epochs": 1, "num_nodes": 1, "init_channels": 1,
                  "batch_size": 128, "stem_multiplier": 3},
    )
    stage.build((8, 8, 3), bench.STEPS_PER_EPOCH)
    trial_settings = {k: v for k, v in rung.items()
                      if k not in ("primitives", "num_train_examples", "num_layers")}
    trial = DartsSearch(primitives=prims, num_layers=3, settings=trial_settings)
    trial.build((8, 8, 3), 8)  # data-derived steps differ; horizon pins the key
    assert trial._search_step is stage._search_step
    cold = DartsSearch(primitives=prims, num_layers=3,
                       settings=dict(trial_settings, schedule_horizon=0))
    cold.build((8, 8, 3), 8)
    assert cold._search_step is not stage._search_step


def test_e2e_plan_tpu_ladder_degrades_to_warm_rung(bench, monkeypatch):
    """A squeezed TPU child budget must fall back to the warm-cache headline
    rung rather than skip the e2e stage outright."""
    monkeypatch.delenv("BENCH_NOMINAL_DARTS_STEP_MS", raising=False)
    monkeypatch.delenv("BENCH_NOMINAL_DARTS_STEP_MS_TPU", raising=False)
    scale, n, _ = bench._e2e_plan(True, 400.0, {"step_ms": 25.0}, 10)
    assert scale["init_channels"] == 8 and n == 10  # plenty: discriminative rung
    scale, n, _ = bench._e2e_plan(True, 60.0, {"step_ms": 25.0}, 10)
    assert scale["init_channels"] == 1 and scale["schedule_horizon"] == 390
    assert bench._e2e_plan(True, 30.0, {"step_ms": 25.0}, 10) is None


def test_e2e_plan_garbage_nominal_override_falls_back(bench, monkeypatch):
    """A zero or non-numeric pin override must fall back to the built-in
    nominal, not crash the e2e stage with ZeroDivisionError/ValueError."""
    for bad in ("0", "banana"):
        monkeypatch.setenv("BENCH_NOMINAL_DARTS_STEP_MS", bad)
        _, _, contention = bench._e2e_plan(False, 900.0, {"step_ms": 2200.0}, 3)
        assert contention == pytest.approx(2.0)  # 2200 / builtin 1100


def test_probe_until_live_exits_on_first_healthy(bench, monkeypatch):
    """A live backend must cost exactly one probe — retries are only for
    wedges, never overhead on the happy path."""
    calls = []

    def probe(budget):
        calls.append(budget)
        return "healthy", "rt 5ms on v5e", 5.0

    verdict, diag, rt, errs = bench._probe_until_live(
        time.time() + 700, probe=probe, sleep=lambda s: None
    )
    assert verdict == "healthy" and rt == 5.0 and errs == []
    assert len(calls) == 1


def test_probe_until_live_retries_through_a_wedge(bench, monkeypatch):
    """Round-4 fix: a wedge that clears mid-window must be survived — the
    old single-shot probe gave up and fell back to CPU (1 TPU capture in 4
    rounds). Simulated clock: two wedged attempts, then recovery."""
    monkeypatch.setenv("BENCH_PROBE_RETRY_SLEEP", "45")
    now = [0.0]
    answers = iter([
        ("dead", "probe timed out after 150s (backend wedged or hung)", None),
        ("dead", "roundtrip 400.0ms > 250.0ms ceiling (backend degraded past use)", None),
        ("degraded", "rt 80ms on v5e", 80.0),
    ])

    def probe(budget):
        now[0] += 150  # each probe consumes its budget
        return next(answers)

    def sleep(s):
        now[0] += s

    verdict, diag, rt, errs = bench._probe_until_live(
        700.0, probe=probe, sleep=sleep, clock=lambda: now[0]
    )
    assert verdict == "degraded" and rt == 80.0
    assert len(errs) == 2 and "attempt 1" in errs[0] and "attempt 2" in errs[1]


def test_probe_until_live_respects_window(bench, monkeypatch):
    """Retries must never eat into the CPU reserve: when the window is gone,
    the loop reports dead with the attempt history."""
    monkeypatch.setenv("BENCH_PROBE_RETRY_SLEEP", "45")
    now = [0.0]

    def probe(budget):
        assert budget <= 150.0 + 1e-9
        now[0] += min(150, budget)
        return "dead", f"probe timed out after {budget:.0f}s (backend wedged)", None

    def sleep(s):
        now[0] += s

    verdict, _, rt, errs = bench._probe_until_live(
        500.0, probe=probe, sleep=sleep, clock=lambda: now[0]
    )
    assert verdict == "dead" and rt is None
    assert 2 <= len(errs) <= 4  # several attempts fit a 500s window, not 50
    assert now[0] <= 500.0 + 150.0  # never sleeps past the window


def test_probe_until_live_fails_fast_on_deterministic_failure(bench, monkeypatch):
    """A fast rc!=0 probe failure (e.g. 'no accelerator backend' on a box
    with no accelerator) is permanent, not a wedge — retrying it would sleep
    away the CPU child's budget. One attempt, immediate dead verdict."""
    monkeypatch.setenv("BENCH_PROBE_RETRY_SLEEP", "45")
    calls = []

    def probe(budget):
        calls.append(budget)
        return "dead", "probe rc=1: AssertionError: no accelerator backend", None

    slept = []
    verdict, diag, rt, errs = bench._probe_until_live(
        time.time() + 700, probe=probe, sleep=slept.append
    )
    assert verdict == "dead" and rt is None
    assert len(calls) == 1 and slept == []
    assert "no accelerator backend" in diag


def test_freshest_tpu_capture_summarizes_watcher_record(bench):
    """The CPU-fallback artifact must carry the newest watcher capture's TPU
    numbers labeled with provenance."""
    cap = bench._freshest_tpu_capture()
    # the repo ships at least one watcher capture (examples/records/)
    assert cap is not None
    assert "NOT measured by this driver run" in cap["provenance"]
    assert cap["file"].startswith("examples/records/bench_tpu_")
    assert cap["captured_at"]
    assert cap["mfu_small"] or cap["headline_value_s"]


def test_obslog_report_throughput_smoke_exercises_buffered_path(bench):
    """--smoke mode of the obslog_report_throughput scenario: the full
    sync-vs-buffered pipeline (enqueue, group commit, read-your-writes
    spot-check, flush barrier) runs end-to-end at a trimmed row count. No
    speed assertion here — CI contention would make a ratio flaky; the ≥5x
    target is the timed run's acceptance number."""
    out = bench._bench_obslog_report_throughput(smoke=True)
    assert out["smoke"] is True
    assert out["rows_complete"] and out["durable_rows"] == out["n_reports"]
    assert out["group_commits"] >= 1
    assert out["max_batch_rows"] >= 1
    assert out["sync_rows_per_s"] > 0 and out["buffered_rows_per_s"] > 0


def test_obslog_fold_latency_smoke_identical(bench):
    """--smoke mode of obslog_fold_latency: the incremental fold index must
    be byte-identical to the fold_observation rescan at every log size
    (non-numeric values and timestamp ties included in the generated logs)."""
    out = bench._bench_obslog_fold_latency(smoke=True)
    assert out["smoke"] is True and out["sizes"]
    assert all(s["identical"] for s in out["sizes"])
    assert all(s["indexed_us"] > 0 and s["rescan_us"] > 0 for s in out["sizes"])


def test_tracing_overhead_smoke_wiring(bench):
    """--smoke mode of the tracing_overhead scenario: two full in-process
    experiments (tracing on and off) run end-to-end at a trimmed trial
    count, and the traced side actually recorded spans. No strict 3%
    assertion here — CI contention would make the ratio flaky; that target
    is the timed run's acceptance number, reported as within_target."""
    out = bench._bench_tracing_overhead(smoke=True)
    assert out["smoke"] is True
    assert out["trials"] == 12 and out["reports_per_trial"] > 0
    assert out["on_s"] > 0 and out["off_s"] > 0
    assert out["on_trials_per_s"] > 0 and out["off_trials_per_s"] > 0
    assert out["target_pct"] == 3.0
    assert isinstance(out["within_target"], bool)
    # no ratio assertion in smoke: the trimmed passes run in ~10ms, where
    # thread-scheduling noise dwarfs tracing cost — the timed (non-smoke)
    # run with busy-work trials is the meaningful <3% measurement


def test_step_stats_overhead_smoke_wiring(bench):
    """--smoke mode of the step_stats_overhead scenario (ISSUE 20): full
    pack_size=8 sweeps run end-to-end with the step-statistics plane off and
    on (off must write zero katib-tpu/perf/ rows and export none of the step
    metric families — asserted inside the scenario), and the final
    injected-straggler pass must fire exactly one GangStraggler event. No
    strict 3% assertion in smoke — the trimmed passes are scheduling noise;
    the timed run's within_target is the acceptance number."""
    out = bench._bench_step_stats_overhead(smoke=True)
    assert out["smoke"] is True
    assert out["pack_size"] == 8 and out["reports_per_member"] > 0
    assert out["on_s"] > 0 and out["off_s"] > 0
    assert out["target_pct"] == 3.0
    assert isinstance(out["within_target"], bool)
    assert out["straggler_events"] == 1


def test_tracing_overhead_distributed_smoke_wiring(bench):
    """--distributed --smoke mode of tracing_overhead (ISSUE 19): the same
    experiment batch runs through 3 REAL replica subprocesses with wire
    tracing off and then on (traceparent on every rpc POST, TDATA frames,
    server-side spans, the durable wire sink), and the traced pass actually
    wrote cross-replica wire records. No strict 3% assertion in smoke —
    the sub-2s passes are scheduling noise; the timed run's within_target
    is the acceptance number."""
    out = bench._bench_tracing_overhead(smoke=True, distributed=True)
    assert out["smoke"] is True and out["distributed"] is True
    assert out["replicas"] == 3
    assert out["experiments"] >= 3 and out["trials"] >= 6
    assert out["on_s"] > 0 and out["off_s"] > 0
    assert out["on_trials_per_s"] > 0 and out["off_trials_per_s"] > 0
    assert out["target_pct"] == 3.0
    assert isinstance(out["within_target"], bool)


def test_telemetry_overhead_smoke_wiring(bench):
    """--smoke mode of the telemetry_overhead scenario: two full in-process
    experiments (sampler on at a 50ms interval, and off) run end-to-end at
    a trimmed trial count. No strict 2% assertion here — CI contention would
    make the ratio flaky; that target is the timed run's acceptance number,
    reported as within_target."""
    out = bench._bench_telemetry_overhead(smoke=True)
    assert out["smoke"] is True
    assert out["trials"] == 12 and out["reports_per_trial"] > 0
    assert out["on_s"] > 0 and out["off_s"] > 0
    assert out["on_trials_per_s"] > 0 and out["off_trials_per_s"] > 0
    assert out["target_pct"] == 2.0
    assert isinstance(out["within_target"], bool)


def test_check_latency_smoke_stays_fast(bench):
    """--smoke analyzer run (ISSUE 6 satellite): the static-analysis pass
    gates every PR from tier-1, so the full-tree pass must stay under a few
    seconds — and must be clean on the shipped tree (the same gate
    tests/test_static_analysis.py::test_tree_is_clean enforces with a
    readable diff)."""
    out = bench._bench_check_latency(smoke=True)
    assert out["smoke"] is True
    assert out["files"] > 80
    assert out["findings"] == 0
    assert out["elapsed_s"] < 5.0, out
    assert out["within_target"] is True


def test_analyze_latency_smoke_stays_fast(bench):
    """--smoke analyzer run (ISSUE 7 satellite): full semantic analysis of
    mnist + transformer under their example search spaces — baseline trace
    plus every corner — must stay under the 5s budget, classify the
    expected parameters, and produce stable fingerprints."""
    out = bench._bench_analyze_latency(smoke=True)
    assert out["smoke"] is True
    assert out["elapsed_s"] < 5.0, out
    assert out["within_target"] is True
    mnist = out["targets"]["mnist"]
    lm = out["targets"]["transformer"]
    assert mnist["fingerprint"].startswith("ktfp-")
    assert mnist["classes"] == {"lr": "runtime-scalar", "momentum": "runtime-scalar"}
    assert lm["classes"] == {
        "learning_rate": "runtime-scalar", "embed_dim": "shape-affecting",
    }
    assert mnist["flops"] > 0 and lm["peak_bytes"] > 0


def test_compile_amortization_smoke_wiring(bench):
    """--smoke mode of the compile_amortization scenario (ISSUE 8): the
    cold (service off, inline synthetic compile) and pre-warmed (service
    on, executable handed via ctx.compiled_program) sweeps both run
    end-to-end, the service compiled/traced the shared program exactly
    once, and the warm side actually skipped the synthetic compile (its
    e2e must undercut the cold side's floor — the synthetic cost — which
    CI contention cannot fake). The >=2x target is the timed run's
    acceptance number, reported as within_target."""
    out = bench._bench_compile_amortization(smoke=True)
    assert out["smoke"] is True
    assert out["trials"] == 6
    assert out["service_compiles"] == 1 and out["service_traces"] == 1
    assert out["cold_s"] >= out["synthetic_compile_cost_s"]
    assert 0 < out["warm_s"] < out["cold_s"]
    assert out["target_speedup"] == 2.0
    assert isinstance(out["within_target"], bool)


def test_pbt_fused_throughput_smoke_wiring(bench):
    """--smoke mode of the pbt_fused_throughput scenario (ISSUE 9): the
    legacy job-queue PBT sweep and the fused lax.scan sweep both run
    end-to-end on the simple_pbt workload, and the fused-vs-stepwise
    lineage parity (chunk=G vs chunk=1 of the identical program, fixed
    seed) holds bit-for-bit. No speed ratio assertion in smoke — trimmed
    walls are scheduler noise; the >=5x target is the timed run's
    acceptance number, reported as within_target."""
    out = bench._bench_pbt_fused_throughput(smoke=True)
    assert out["smoke"] is True
    assert out["lineage_bit_identical"] is True
    assert out["fused_generations"] == 6
    assert out["legacy_generations"] >= 1
    assert out["fused_gen_per_s"] > 0 and out["legacy_gen_per_s"] > 0
    assert out["target_speedup"] == 5.0
    assert isinstance(out["within_target"], bool)


def test_suggestion_throughput_smoke_parity(bench):
    """--smoke mode of the suggestion_throughput scenario (ISSUE 10): the
    batched jitted TPE / CMA-ES / BO kernels and the legacy NumPy
    suggesters run on identical seeded histories and the vectorized
    selections must match the oracle within fp tolerance. No speed ratio
    assertion in smoke — trimmed kernels are dominated by dispatch
    overhead; the timed run reports measured speedups + target verdicts
    (the >=5x target assumes an accelerator backend — see the scenario
    docstring and docs/suggestion-plane.md)."""
    out = bench._bench_suggestion_throughput(smoke=True)
    assert out["smoke"] is True
    assert out["parity_exact"] is True
    assert set(out["algos"]) == {"tpe", "cmaes", "bayesianoptimization"}
    for algo, rec in out["algos"].items():
        assert rec["parity_err"] < 1e-6, (algo, rec)
        assert rec["legacy_cands_per_s"] > 0 and rec["vectorized_cands_per_s"] > 0
    assert out["target_speedup"] == 5.0


def test_suggestion_pipeline_latency_smoke_integrity(bench):
    """--smoke mode of the suggestion_pipeline_latency scenario (ISSUE
    10): inline and async sweeps both complete with zero duplicate or lost
    assignments. The >=3x span-ratio assertion belongs to the timed run
    (trimmed sweeps are scheduler noise); smoke pins the wiring and the
    integrity invariant."""
    out = bench._bench_suggestion_pipeline_latency(smoke=True)
    assert out["smoke"] is True
    assert out["trials"] == 8
    assert out["inline_mean_span_ms"] > 0
    assert out["async_mean_span_ms"] > 0
    assert out["target_ratio"] == 3.0
    assert isinstance(out["within_target"], bool)


def test_asha_device_seconds_smoke_integrity(bench):
    """--smoke mode of the asha_device_seconds scenario (ISSUE 11): both
    sweeps complete, promotions fire, and zero observations are lost
    across promotions (fold-index totals byte-identical to row scans,
    every epoch curve continuous). The >=5x device-epoch assertion belongs
    to the full-size run (the smoke ladder is too short for it); smoke
    pins the wiring and the integrity invariants."""
    out = bench._bench_asha_device_seconds(smoke=True)
    assert out["smoke"] is True
    assert out["configs"] == 9
    assert out["lost_observations"] == 0
    assert out["promotions"] > 0
    assert out["asha_device_epochs"] < out["flat_device_epochs"]
    assert out["target_reached"] is True
    assert out["target_ratio"] == 5.0
    assert isinstance(out["within_target"], bool)


def test_bohb_convergence_smoke_integrity(bench):
    """--smoke mode of the bohb_convergence scenario (ISSUE 13): BOHB and
    ASHA race the same ladder with zero lost observations, dwell-batched
    promotions dispatch as ceil(promotions/pack_capacity) groups (not one
    per promotion), per-bracket device-epochs are recorded separately, and
    the warm run consumes the cold run's history (WarmStartApplied, model
    armed from batch 1). The <=0.7x epochs-to-target and warm<=cold race
    assertions belong to the full-size run (the smoke ladder is too short
    for timing claims); smoke pins the wiring and the integrity
    invariants."""
    out = bench._bench_bohb_convergence(smoke=True)
    assert out["smoke"] is True
    assert out["configs"] == 9
    assert out["lost_observations"] == 0
    assert out["bohb_promotions"] > 0
    # crossing the target at all hinges on the one top-rung stint, which
    # the 9-config smoke ladder cannot guarantee — the values are reported
    # (possibly null) and asserted only at full size
    assert "asha_epochs_to_target" in out and "bohb_epochs_to_target" in out
    pack = out["promotion_pack"]
    assert pack["dispatch_groups"] == pack["expected_groups"] < pack["promotions"]
    assert pack["batched_events"] >= 1
    assert set(out["per_bracket_device_epochs"]) == {"0", "1"}
    assert out["warm_start_applied"] is True
    assert out["target_ratio"] == 0.7
    assert isinstance(out["within_target"], bool)


def test_device_chaos_recovery_smoke_integrity(bench):
    """--smoke mode of the device_chaos_recovery scenario (ISSUE 12): the
    chaos run (1 wedged probe + 2 device revocations) completes with zero
    lost observations, preempted trials resume to success bit-identically,
    and the wedged probe costs a bounded attempt — never the round. The
    1.5x wall-clock ceiling belongs to the full-size run; smoke pins the
    wiring and the integrity invariants."""
    out = bench._bench_device_chaos_recovery(smoke=True)
    assert out["smoke"] is True
    assert out["trials"] == 8
    assert out["lost_observations"] == 0
    assert out["trials_preempted"] >= 1
    assert out["bit_identical"] is True
    assert out["device_lost_events"] >= 2
    assert out["probe_seconds"] < 10.0
    assert out["free_devices_after_chaos"] == out["devices"] - 2
    assert out["target_ratio"] == 1.5
    assert isinstance(out["within_target"], bool)


def test_controller_kill_recovery_smoke_integrity(bench):
    """--smoke mode of the controller_kill_recovery scenario (ISSUE 14):
    the checkpointed sweep survives >= 2 controller SIGKILLs (journal-
    counter-keyed chaos kills of real subprocess controllers) with zero
    lost observations, score rows bit-identical to the fault-free run, and
    every recovery replay bounded under 10s."""
    out = bench._bench_controller_kill_recovery(smoke=True)
    assert out["smoke"] is True
    assert out["sigkills_injected"] >= 2
    assert out["lost_observations"] == 0
    assert out["bit_identical"] is True
    assert out["recovery_replays"] >= 2
    assert out["max_replay_seconds"] < out["replay_bound_seconds"] == 10.0


def test_control_plane_scaling_smoke_integrity(bench):
    """--smoke mode of the control_plane_scaling scenario (ISSUE 15): the
    load harness drives the same experiment batch through 1 and then 2
    REAL replica subprocesses over the HTTP wire protocol, SIGKILLs one
    replica mid-run, and the survivors fail its experiments over inside
    the placement-lease TTL with zero lost observations and rows
    bit-identical to the fault-free run. The >= 2.5x aggregate-throughput
    assertion belongs to the full-size (3-replica) run; smoke pins the
    wiring and the integrity invariants."""
    out = bench._bench_control_plane_scaling(smoke=True)
    assert out["smoke"] is True
    assert out["replicas"] == 2
    assert out["lost_observations"] == 0
    assert out["bit_identical"] is True
    assert out["failovers"] >= 1
    assert out["victim_experiments"] >= 1
    assert out["max_failover_seconds"] < out["failover_bound_seconds"]
    assert out["speedup"] > 0


def test_multi_tenant_scaling_smoke_integrity(bench):
    """--smoke mode of the multi_tenant_scaling scenario (ISSUE 17): four
    tenants drive namespaced experiments through 2 REAL replica
    subprocesses with the tenancy plane armed — per-tenant tokens, shared
    admission buckets, an adversarial cross-tenant probe (zero leaks), the
    starved low-quota tenant still progressing, and a mid-run SIGKILL with
    zero lost observations. The >= 0.9x throughput-vs-baseline assertion
    belongs to the full-size (3-replica, 8-tenant) run; smoke pins the
    wiring and the isolation invariants."""
    out = bench._bench_multi_tenant_scaling(smoke=True)
    assert out["smoke"] is True
    assert out["replicas"] == 2
    assert out["cross_tenant_leaks"] == 0
    assert out["lost_observations"] == 0
    assert out["bit_identical"] is True
    assert out["starved_tenant_trials"] > 0
    assert out["probe_grants"][out["starved_tenant"]] < max(
        out["probe_grants"].values()
    )
    assert out["sigkill_victim"]
    assert out["throughput_ratio"] > 0


def test_ingest_throughput_smoke_integrity(bench):
    """--smoke mode of the ingest_throughput scenario (ISSUE 16): the same
    streaming workload lands once over the HTTP/JSON wire and once over
    the framed ingest plane with a mid-stream replica SIGKILL — streamers
    reroute to the survivors, the idempotent duplicate drop absorbs the
    resends, and the full deterministic row set verifies offline exactly
    once, bit-identical. The >= 5x rows/sec assertion belongs to the
    full-size (3-replica, thousands-of-experiments) run; smoke pins the
    wiring and the integrity invariants."""
    out = bench._bench_ingest_throughput(smoke=True)
    assert out["smoke"] is True
    assert out["replicas"] == 2
    assert out["lost_observations"] == 0
    assert out["bit_identical"] is True
    assert out["sigkill_victim"]
    assert out["rows_per_sec_json"] > 0
    assert out["rows_per_sec_framed_chaos"] > 0


def test_obslog_scenarios_run_standalone_via_cli():
    """`python bench.py obslog_report_throughput --smoke` prints one JSON
    line — the documented entry point for the data-plane scenarios."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "obslog_report_throughput", "--smoke"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-500:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1
    parsed = json.loads(lines[0])
    assert parsed["metric"] == "obslog_report_throughput"
    assert parsed["rows_complete"] is True


def test_sentinel_carries_freshest_capture(bench, monkeypatch, capsys):
    """Even the all-dead sentinel line ships the labeled watcher numbers."""
    monkeypatch.setenv("BENCH_TOTAL_BUDGET", "40")  # too small for anything
    monkeypatch.setenv("BENCH_FORCE_CPU", "1")
    monkeypatch.setattr(bench, "_run_child", lambda *a, **k: (None, "stubbed dead"))
    bench.main()
    line = [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")][-1]
    payload = json.loads(line)
    assert payload["value"] == -1.0
    assert payload["extras"]["freshest_tpu_capture"]["captured_at"]
