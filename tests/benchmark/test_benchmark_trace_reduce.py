"""trace_reduce.py on the recorded trace (trimmed from PR 26's first traced chip
run of the steady cell) and on a synthetic one: busy union, idle share, kernel
time by name."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import trace_reduce  # noqa: E402


def _plane(name, line, events):
    return {"name": name, "lines": [{"name": line, "events": events}]}


def test_union_merges_nested_and_touching_intervals():
    assert trace_reduce.union([(5, 9), (0, 4), (3, 6), (20, 30), (22, 25), (30, 31)]) == [(0, 9), (20, 31)]
    assert trace_reduce.union([(3, 3)]) == []


def test_synthetic_trace_busy_idle_and_gap_names():
    trace = {"planes": [
        _plane("/device:TPU:0", "XLA Ops", [
            ["%a f32[8] fusion", 100, 200], ["%b inside a", 150, 50], ["%k bf16[8] custom-call tpu_custom_call", 600, 100],
            ["%late outside the window", 1500, 100]]),
        _plane("/device:TPU:0 ", "Steps", [["0", 0, 1000]]),
        _plane("/host:CPU", "python3", [["bench:traced_window", 0, 1000], ["bench:trial_function", 290, 320],
                                        ["bench:report", 310, 280]]),
    ]}
    r = trace_reduce.reduce(trace)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(300e-9)           # the nested op counted once
    assert r["op_seconds"]["%k bf16[8] custom-call tpu_custom_call"] == pytest.approx(100e-9)
    assert "%late outside the window" not in r["op_seconds"]
    gaps = dict(r["idle_gaps"])
    assert gaps["bench:report"] == pytest.approx(300e-9)   # 300..600, mostly under the report
    assert gaps["unattributed"] == pytest.approx(400e-9)   # 0..100 and 700..1000
    assert trace_reduce.reduce({"planes": trace["planes"][2:]}) is None


def test_two_devices_are_averaged():
    trace = {"planes": [
        _plane("/device:TPU:0", "XLA Ops", [["%a", 0, 100]]),
        _plane("/device:TPU:1", "XLA Ops", [["%a", 0, 50]]),
        _plane("/host:CPU", "python3", [["bench:traced_window", 0, 100]]),
    ]}
    r = trace_reduce.reduce(trace)
    assert r["devices"] == 2 and r["busy_s"] == pytest.approx(75e-9)


def test_short_name_keeps_the_kernel_target():
    hlo = ('%attn.14 = (bf16[64,2048,128]{2,1,0:T(8,128)(2,1)S(1)}, f32[64,2048,1]{2,1,0}) '
           'custom-call(bf16[64,2048,128]{2,1,0} %bitcast.649), custom_call_target="tpu_custom_call"')
    short = trace_reduce.short_name(hlo)
    assert short.startswith("%attn.14 ") and short.endswith("custom-call tpu_custom_call")
    assert len(short) < 120


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(ROOT, "benchmarks", "trace_sample.json")) as f:
        return trace_reduce.reduce(json.load(f))


def test_recorded_trace_window_and_busy_union(recorded):
    assert recorded["devices"] == 1
    assert recorded["window_s"] == pytest.approx(0.4)
    # the steady cell keeps the device busy: 0.3983 s of operations in 0.4 s
    assert recorded["busy_s"] == pytest.approx(0.398333196, rel=1e-6)
    assert 100 * (1 - recorded["busy_s"] / recorded["window_s"]) == pytest.approx(0.417, abs=0.01)
    # async copies overlap the operations: their plain sum would pass the window
    assert sum(recorded["op_seconds"].values()) >= recorded["busy_s"]


def test_recorded_trace_kernel_time_by_name(recorded):
    kernels = {k: v for k, v in recorded["op_seconds"].items() if "tpu_custom_call" in k}
    assert len(kernels) == 12                       # forward, dq, dk/dv in each of 4 layers
    assert sum(recorded["op_counts"][k] for k in kernels) == 32
    assert sum(kernels.values()) == pytest.approx(0.038080233, rel=1e-6)
    assert recorded["device_ops"][0][0].startswith("%fusion.69 ")
    assert len(recorded["device_ops"]) == 10
