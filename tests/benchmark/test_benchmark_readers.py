"""Each per-layer reader on plain data: what it reads, and that a reader with
nothing to read returns nothing — never a 0 for a share of a peak."""

import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path.insert(0, BENCH)

import flops  # noqa: E402
import window  # noqa: E402
from rundata import RunData  # noqa: E402

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
with open(os.path.join(BENCH, "configs", "olmo-1b-d4.json")) as _f:
    CONFIG = json.load(_f)
STEADY = {"opens_after": {"reports": 2}, "closes_on": "report"}
SWEEP = {"opens_after": {"trials": 2}, "closes_on": "trial"}


def _read(metric, run):
    with open(os.path.join(BENCH, "layer_metrics", f"{metric}.json")) as f:
        spec = json.load(f)
    return importlib.import_module(f"readers.{spec['reader']}").read(run, **spec.get("args", {}))


def _run(rule, reports, terminals, seconds=50.0, **kw):
    cell = {"window": rule, "batch_size": 4, "seq_len": 2048, "chips": 1}
    return RunData(
        cell=cell, config=CONFIG, peaks=PEAKS, t_start=0.0,
        window=window.measure(rule, seconds, reports, terminals), reports=reports,
        terminals=terminals, compiles=kw.get("compiles", []), spans=kw.get("spans", {}),
        trace=kw.get("trace"))


def _trial(name, start, compile_s, steps_s, suggest_s=0.01, tail=0.3):
    end = start + 0.2 + compile_s + steps_s + tail
    spans = [
        {"name": "trial", "start": start, "end": end},
        {"name": "suggestion", "start": start, "end": start + suggest_s},
        {"name": "compile", "start": start + 0.2, "end": start + 0.2 + compile_s},
        {"name": "steps", "start": start + 0.2 + compile_s, "end": start + 0.2 + compile_s + steps_s},
    ]
    return name, spans, end


def test_steady_readers():
    reports = [(10.0 + 0.8 * i + (0.5 if i == 7 else 0.0), 5) for i in range(40)]
    run = _run(STEADY, reports, [], seconds=20.0, compiles=[(5.0, 20.0), (12.0, 0.5)])
    w = run.window
    assert _read("compiles_in_window", run) == 1.0
    assert _read("report_gap_max_ms", run) == pytest.approx(1300.0)
    need = w.steps * flops.train_step_flops(CONFIG, 4, 2048)
    assert _read("train_step_mfu", run) == pytest.approx(100 * need / (w.seconds * 197e12))
    assert _read("device_idle_share.steady", run) is None      # no trace, nothing to read
    assert _read("flash_roofline", run) is None


def test_device_readers_on_a_reduced_trace():
    trace = {"window_s": 5.0, "busy_s": 4.9, "devices": 1,
             "op_seconds": {"%attn.1 custom-call tpu_custom_call": 0.3, "%fusion.2 fusion": 4.0},
             "op_counts": {"%attn.1 custom-call tpu_custom_call": 300, "%fusion.2 fusion": 100}}
    run = _run(STEADY, [(1.0 + i, 5) for i in range(9)], [], seconds=5.0, trace=trace)
    assert _read("device_idle_share.steady", run) == pytest.approx(2.0)
    product = 4 * 16 * 2048 * 2048 * 128
    least = 100 * (2 * product + 2 * 2.5 * product) / 197e12      # 100 layers' worth of calls
    assert _read("flash_roofline", run) == pytest.approx(100 * least / 0.3)
    assert _read("flash_roofline", run) < 100
    no_kernel = dict(trace, op_seconds={"%fusion.2 fusion": 4.0}, op_counts={"%fusion.2 fusion": 100})
    assert _read("flash_roofline", _run(STEADY, [(1.0, 5), (2.0, 5), (3.0, 5)], [], trace=no_kernel)) is None


def test_sweep_readers():
    spans, terminals, reports, start = {}, [], [], 0.0
    for name in "abcd":
        trial, tree, end = _trial(name, start, compile_s=15.0, steps_s=2.0)
        spans[trial] = tree
        reports += [(end - 1.5, 5), (end - 0.4, 5)]
        terminals.append((end, trial, "Succeeded"))
        start = end + 0.6                                        # the run loop's wait
    compiles = [(t - 2.5, 14.0) for t, _, _ in terminals] + [(t - 17.0, 0.4) for t, _, _ in terminals]
    run = _run(SWEEP, reports, terminals, seconds=40.0, compiles=compiles, spans=spans)
    assert run.window.trials == ("c", "d")
    assert _read("suggest_s_per_trial", run) == pytest.approx(0.01)
    assert _read("dispatch_s_per_trial", run) == pytest.approx(0.5 - 0.01 + 0.6)
    assert _read("compile_s_per_trial", run) == pytest.approx(14.4)
    need = 20 * flops.train_step_flops(CONFIG, 4, 2048)
    assert _read("sweep_mfu", run) == pytest.approx(100 * need / (run.window.seconds * 197e12))
    assert _read("device_idle_share.sweep", run) is None


def test_a_window_with_no_completion_gives_the_readers_nothing():
    run = _run(SWEEP, [], [(1.0, "a", "Succeeded"), (2.0, "b", "Succeeded")], seconds=10.0)
    for metric in ("suggest_s_per_trial", "dispatch_s_per_trial", "compile_s_per_trial", "sweep_mfu"):
        assert _read(metric, run) is None
