"""The readers of what the program records about itself (PR 27): the step
ledger on the ``steps`` span, the children of the ``compile`` span, the flash
kernels by the names the program gives them. Values by hand on recorded spans
and on the recorded trace with its kernels renamed; nothing to read gives
None; and the whole run on the CPU at the tiny size gives every
``program_span`` metric, with the ledger's steps in the window equal to the
harness's own count."""

import argparse
import importlib
import json
import os
import re
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path.insert(0, BENCH)

import flops  # noqa: E402
import trace_reduce  # noqa: E402
import window  # noqa: E402
from readers import step_ledger  # noqa: E402
from rundata import RunData  # noqa: E402

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
with open(os.path.join(BENCH, "configs", "olmo-1b-d4.json")) as _f:
    CONFIG = json.load(_f)
STEADY = {"opens_after": {"reports": 2}, "closes_on": "report"}
FIELDS = ["t_end", "seconds", "steps", "dispatch_s", "wait_s", "report_s", "store_s"]
LEDGER = ["step_host_ms", "step_dispatch_ms", "device_wait_max_ms", "report_call_max_ms"]
SETUP = ["trial_build_s", "trial_compile_s"]
KERNELS = ["flash_fwd_roofline", "flash_dq_roofline", "flash_dkv_roofline"]


def _read(metric, run):
    with open(os.path.join(BENCH, "layer_metrics", f"{metric}.json")) as f:
        spec = json.load(f)
    return importlib.import_module(f"readers.{spec['reader']}").read(run, **spec.get("args", {}))


def _run(reports, spans=None, trace=None, seconds=50.0):
    cell = {"window": STEADY, "batch_size": 4, "seq_len": 2048, "chips": 1}
    return RunData(
        cell=cell, config=CONFIG, peaks=PEAKS, t_start=0.0,
        window=window.measure(STEADY, seconds, reports, []), reports=reports, terminals=[],
        compiles=[], spans=spans or {}, trace=trace)


def _recorded():
    """One trial: compile 2.0 .. 20.0 with its children, a report every 0.8 s
    from 20.0 (entered 2 ms before it leaves), the tenth interval stalled by
    1.5 s of waiting for the device."""
    reports, rows, exit_before = [], [], 20.0
    for i in range(1, 40):
        stall = 1.5 if i == 10 else 0.0
        leave = exit_before + 0.8 + stall
        reports.append((leave - 0.002, 5))          # the harness stamps the entry
        rows.append([leave, 0.8 + stall, 5, 0.005, 0.790 + stall, 0.002, 0.0005])
        exit_before = leave
    reports.insert(0, (19.998, 5))                  # the first report: no interval
    spans = [
        {"name": "trial", "spanId": "r", "parentId": None, "start": 1.0, "end": exit_before + 1},
        {"name": "compile", "spanId": "c", "parentId": "x", "start": 2.0, "end": 20.0},
        {"name": "build", "spanId": "b", "parentId": "c", "start": 2.0, "end": 5.0},
        {"name": "stage_batch", "spanId": "s", "parentId": "c", "start": 5.0, "end": 5.25},
        {"name": "jaxpr_trace", "spanId": "j", "parentId": "c", "start": 5.5, "end": 7.5},
        {"name": "lower", "spanId": "l", "parentId": "c", "start": 7.5, "end": 8.5},
        {"name": "backend_compile", "spanId": "k", "parentId": "c", "start": 8.5, "end": 18.5},
        {"name": "backend_compile", "spanId": "o", "parentId": "elsewhere", "start": 3.0, "end": 4.0},
        {"name": "steps", "spanId": "t", "parentId": "x", "start": 20.0, "end": exit_before,
         "attrs": {"interval_fields": FIELDS, "intervals": rows, "reports": 40}},
    ]
    return reports, {"bench-1": spans}


def test_ledger_readers_on_recorded_spans():
    reports, spans = _recorded()
    run = _run(reports, spans, seconds=20.0)
    w = run.window
    inside = step_ledger.intervals(run)
    # the window opens at the second report: its interval is outside, the next one inside
    assert sum(i["steps"] for i in inside) == w.steps == 5 * w.reports
    assert sum(i["seconds"] for i in inside) == pytest.approx(w.seconds, rel=1e-3)
    assert _read("step_dispatch_ms", run) == pytest.approx(1.0)                 # 5 ms over 5 steps
    assert _read("step_host_ms", run) == pytest.approx(1.0)                     # 0.8 - 0.005 - 0.790
    assert _read("device_wait_max_ms", run) == pytest.approx(2290.0)            # the stalled interval
    assert _read("report_call_max_ms", run) == pytest.approx(2.0)
    assert _read("trial_build_s", run) == pytest.approx(3.25)
    assert _read("trial_compile_s", run) == pytest.approx(13.0)                 # its own children only


def test_a_stall_outside_the_window_is_not_read():
    reports, spans = _recorded()
    run = _run(reports, spans, seconds=5.0)                                     # closes before interval 10
    assert _read("device_wait_max_ms", run) == pytest.approx(790.0)


@pytest.mark.parametrize("metric", LEDGER + SETUP + KERNELS)
def test_nothing_recorded_gives_none(metric):
    reports, spans = _recorded()
    bare = {"bench-1": [dict(s, attrs={}) for s in spans["bench-1"]
                        if s["name"] in ("trial", "compile", "steps")]}      # the parent commit's spans
    trace = {"window_s": 5.0, "busy_s": 4.9, "devices": 1,
             "op_seconds": {"%attn.1 bf16[64] custom-call tpu_custom_call": 0.3},
             "op_counts": {"%attn.1 bf16[64] custom-call tpu_custom_call": 300}}
    assert _read(metric, _run(reports, bare, trace=trace)) is None
    assert _read(metric, _run(reports, {}, trace=None)) is None


def test_a_trial_begun_inside_the_window_is_not_set_up():
    reports, spans = _recorded()
    late = {"bench-2": [dict(s, start=s["start"] + 30.0, end=s["end"] + 30.0) for s in spans["bench-1"]
                        if s["name"] != "steps"]}
    run = _run(reports, dict(spans, **late), seconds=20.0)
    assert _read("trial_build_s", run) == pytest.approx(3.25)


def _renamed_sample():
    """The recorded trace with its kernels named as this program names them. By
    their results: ``(bf16[..], f32[..])`` is the forward kernel (output and row
    statistics), one bf16 result dq, ``(bf16[..], bf16[..])`` dk/dv."""
    with open(os.path.join(BENCH, "trace_sample.json")) as f:
        trace = json.load(f)
    for plane in trace["planes"]:
        for line in plane["lines"]:
            for e in line["events"]:
                m = re.match(r"%attn\.(\d+) (\()?", e[0])
                if m:
                    kernel = ("flash_bwd_dq" if not m.group(2) else
                              "flash_fwd" if ", f32" in e[0] else "flash_bwd_dkv")
                    e[0] = e[0].replace(f"%attn.{m.group(1)}", f"%{kernel}.{m.group(1)}", 1)
    return trace


def test_kernel_readers_on_the_recorded_trace_with_renamed_kernels():
    reduced = trace_reduce.reduce(_renamed_sample())
    run = _run([(1.0 + i, 5) for i in range(9)], trace=reduced, seconds=5.0)
    product = 4 * 16 * 2048 * 2048 * 128
    for metric, kernel, operations in (("flash_fwd_roofline", "flash_fwd", 2.0 * product),
                                       ("flash_dq_roofline", "flash_bwd_dq", 2.5 * product),
                                       ("flash_dkv_roofline", "flash_bwd_dkv", 2.5 * product)):
        names = [n for n in reduced["op_seconds"] if n.startswith(f"%{kernel}.")]
        spent = sum(reduced["op_seconds"][n] for n in names)
        calls = sum(reduced["op_counts"][n] for n in names)
        assert calls > 0
        assert _read(metric, run) == pytest.approx(100 * calls * operations / 197e12 / spent)
        assert 0 < _read(metric, run) < 100
    # the three shares, weighted by their device time, are the share over all of them
    shares = {m: _read(m, run) for m in KERNELS}
    spent = {m: sum(v for n, v in reduced["op_seconds"].items() if n.startswith(f"%{k}."))
             for m, k in zip(KERNELS, ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))}
    cost = flops.flash_attention_cost(4, 2048, 16, 128)
    calls = {m: sum(c for n, c in reduced["op_counts"].items() if n.startswith(f"%{k}."))
             for m, k in zip(KERNELS, ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))}
    weighted = sum(shares[m] * spent[m] for m in KERNELS) / sum(spent.values())
    least = sum(calls[m] * flops.roofline_seconds(*cost[part], PEAKS)[0]
                for m, part in zip(KERNELS, ("forward", "backward_each", "backward_each")))
    assert weighted == pytest.approx(100 * least / sum(spent.values()))
    # the accepted reader still finds them; it credits every call a third of a layer, which is
    # the same number where each kernel ran as often (the 0.4 s cut holds 8, 12 and 12 calls)
    assert calls == dict(zip(KERNELS, (8, 12, 12)))
    assert _read("flash_roofline", run) == pytest.approx(weighted, rel=0.05)


def test_the_whole_run_on_the_cpu_gives_every_program_span_metric(tmp_path):
    import run as run_module
    from experiment import load_cell

    cell, config = load_cell("tiny.steady")
    args = argparse.Namespace(workload="tiny.steady", seed=27, seconds=2.0, trace=0)
    seen = run_module.drive(args, cell, config)
    try:
        # the killed trial's spans were persisted when the controller closed
        spans = run_module.read_spans(seen["root"], seen["experiment"])
        assert len(spans) == 1
        data = RunData(
            cell=cell, config=config, peaks=PEAKS, t_start=run_module.T_START, window=seen["window"],
            reports=seen["reports"], terminals=seen["terminals"], compiles=seen["compiles"],
            spans=spans, trace=None)
        w = data.window
        inside = step_ledger.intervals(data)
        assert sum(i["steps"] for i in inside) == w.steps > 0
        assert len(inside) == w.reports
        assert sum(i["seconds"] for i in inside) == pytest.approx(w.seconds, rel=0.05)
        for metric in LEDGER + SETUP:
            value = _read(metric, data)
            assert isinstance(value, float) and value > 0, metric
        for i in inside:
            assert i["dispatch_s"] + i["wait_s"] + i["report_s"] <= i["seconds"] + 1e-6
            assert 0 <= i["store_s"] <= i["report_s"]
        names = {s["name"] for s in next(iter(spans.values()))}
        assert {"compile", "build", "stage_batch", "jaxpr_trace", "lower", "backend_compile",
                "steps"} <= names
    finally:
        shutil.rmtree(seen["root"], ignore_errors=True)
