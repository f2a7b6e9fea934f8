"""bench.py's scenarios, wired: each of the 17 CPU rehearsals of the control
plane runs once in ``--smoke`` mode and is held to what it found (counts,
identities, parities), never to how long it took: six workers share this
machine's cores, so a bound on the host's clock fails without a fault."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def bench():
    from tests.conftest import load_bench_module

    return load_bench_module()


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


def test_check_latency_smoke_finds_the_tree_clean(bench):
    """--smoke analyzer run (ISSUE 6 satellite): the static-analysis pass
    walks the whole shipped tree and finds nothing (the same gate
    tests/test_static_analysis.py::test_tree_is_clean enforces with a
    readable diff). No speed assertion here — CI contention would make it
    flaky."""
    out = bench._bench_check_latency(smoke=True)
    assert out["smoke"] is True
    assert out["files"] > 80
    assert out["findings"] == 0


def test_analyze_latency_smoke_classifies_and_fingerprints(bench):
    """--smoke analyzer run (ISSUE 7 satellite): full semantic analysis of
    mnist + transformer under their example search spaces — baseline trace
    plus every corner — classifies the expected parameters and produces
    stable fingerprints. No speed assertion here either."""
    out = bench._bench_analyze_latency(smoke=True)
    assert out["smoke"] is True
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


@pytest.mark.parametrize("argv", [[], ["no_such_scenario"]], ids=["no-name", "unknown-name"])
def test_without_a_scenario_it_exits_2_and_lists_them(bench, argv):
    """bench.py starts nothing it was not asked for: with no or an unknown
    name it exits 2, prints no result line and names every scenario."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), *argv],
        capture_output=True, text=True, timeout=60, cwd=REPO,
    )
    assert proc.returncode == 2, (proc.stdout[-300:], proc.stderr[-300:])
    assert proc.stdout.strip() == ""
    assert len(bench.SCENARIOS) == 17
    for name in bench.SCENARIOS:
        assert name in proc.stderr
