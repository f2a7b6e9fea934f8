"""trace_parts.py: the device's time by the step's parts, on a synthetic trace
and on the recorded ones; what it keeps of trace_reduce's numbers; the
``Hlo Proto`` join on a real profile; the ``device_parts`` reader."""

import glob
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import trace_parts  # noqa: E402
import trace_reduce  # noqa: E402
from readers import device_parts  # noqa: E402

STEP = "jit_step(7)"
FWD = "jit(step)/jvp(TransformerLM)/block0"
BWD = "jit(step)/transpose(jvp(TransformerLM))/block0"


def _synthetic(step_at=590):
    """Two whole runs of the step in the window (50..1200), a third cut by its
    end, another module's run between, and the runs the trace's start and end
    may have cut; the host's annotations around them."""
    ops = [
        # run 1, 100..500: a while loop with a kernel inside it
        ["%a", 100, 100, f"{FWD}/attn/attention/dot"],
        ["%w while", 200, 200, f"{FWD}/attn/linear_attention/while"],
        ["%k kernel", 250, 100, f"{BWD}/attn/linear_attention/kda_parts/vjp"],
        ["%o", 400, 80, "jit(step)/optimizer/add"],
        # run 2, 600..900: a copy that starts inside an operation and outlasts it
        ["%a", 600, 150, f"{FWD}/attn/attention/dot"],
        ["%c copy", 700, 100, ""],
        ["%o", 820, 60, "jit(step)/optimizer/add"],
        # another module, and a run the window cuts
        ["%x", 920, 50, "jit(other)/x"],
        ["%a", 1000, 150, f"{FWD}/attn/attention/dot"],
    ]
    modules = [[STEP, 0, 40], [STEP, 100, 400], [STEP, 600, 300], ["jit_other(3)", 920, 50],
               [STEP, 1000, 300], [STEP, 1400, 100]]
    host = [
        ["bench:traced_window", 50, 1150],
        ["katib:step", 40, 55], ["katib:fetch", 470, 30], ["katib:report", 500, 90],
        ["katib:step", step_at, 8], ["bench:report", 860, 70], ["katib:store_write", 870, 55],
        ["katib:step", 990, 8],
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Modules", "events": modules},
                                            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]},
    ]}


def test_parts_of_whole_step_runs_sum_to_their_busy_union():
    r = trace_parts.reduce(_synthetic())
    assert r["step_runs"] == 2
    parts = r["part_seconds"]
    assert set(parts) == set(trace_parts.STEP_PARTS) | {"unnamed"}
    ns = {k: [round(x * 1e9, 6) for x in v] for k, v in parts.items() if any(v)}
    # the kernel inside the loop is its own part; the loop keeps the rest of its time;
    # the copy that started last takes what it overlaps; the other module and the cut run count nothing
    assert ns == {"attention": [200, 0], "linear_attention": [100, 0], "kda_parts": [0, 100],
                  "optimizer": [140, 0], "unnamed": [100, 0]}
    assert sum(sum(v) for v in parts.values()) == pytest.approx((380 + 260) * 1e-9)


def test_idle_gaps_keep_bench_names_and_name_the_rest_by_katib_annotations():
    trace = _synthetic()
    gaps = dict(trace_parts.reduce(trace)["idle_gaps"])
    assert gaps["bench:report"] == pytest.approx(40e-9)   # the store write inside it does not take it
    assert gaps["katib:report"] == pytest.approx(120e-9)  # the pause after a report's fetch
    assert gaps["katib:step"] == pytest.approx(50e-9)
    assert gaps["unattributed"] == pytest.approx(100e-9)
    old = dict(trace_reduce.reduce(trace_parts._plain(trace))["idle_gaps"])
    assert old == {"bench:report": pytest.approx(40e-9), "unattributed": pytest.approx(270e-9)}


@pytest.mark.parametrize("step_at,lead_us", [(590, 0.01), (610, -0.01)])
def test_dispatch_lead_pairs_steps_and_runs_after_the_first_report(step_at, lead_us):
    r = trace_parts.reduce(_synthetic(step_at))
    assert r["dispatch_lead_us"] == pytest.approx(lead_us)


def test_no_op_name_and_no_module_line_give_no_parts():
    trace = _synthetic()
    for line in trace["planes"][0]["lines"]:
        line["events"] = [e[:3] for e in line["events"]]
    assert trace_parts.reduce(trace)["part_seconds"] is None
    trace["planes"][0]["lines"] = trace["planes"][0]["lines"][1:]
    r = trace_parts.reduce(trace)
    assert r["part_seconds"] is None and r["step_runs"] == 0 and r["dispatch_lead_us"] is None


def test_trace_reduce_keys_are_kept_to_the_bit_on_the_recorded_trace():
    with open(os.path.join(ROOT, "benchmarks", "trace_sample.json")) as f:
        trace = json.load(f)
    old, new = trace_reduce.reduce(trace), trace_parts.reduce(trace)
    assert {k: new[k] for k in old} == old
    assert new["part_seconds"] is None


def test_hlo_proto_join_names_a_fusion_by_its_root(tmp_path):
    """The ``Hlo Proto`` of a module the CPU ran, from a real profile: the
    fused instructions of a scoped function carry the part."""
    import jax
    import jax.numpy as jnp

    from katib_tpu.tracing import step_part

    def f(x):
        with step_part("optimizer"):
            y = jnp.sin(x) * 2.0
        return (y @ y.T).sum()

    g = jax.jit(f)
    x = jnp.ones((64, 64))
    g(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    g(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))[0]
    with open(path, "rb") as fh:
        metadata = trace_parts._planes_metadata(fh.read())
    protos = dict(metadata[trace_parts.METADATA_PLANE])
    module = next(name for name in protos if name.startswith("jit_f("))
    names = trace_parts.hlo_op_names(protos[module]["Hlo Proto"])
    parts = {trace_parts.part_of(op)[0] for op in names.values() if op}
    assert "optimizer" in parts
    assert any(name.endswith("fusion") and trace_parts.part_of(op)[0] == "optimizer"
               for name, op in names.items())


def test_device_parts_reads_ms_a_step_and_shares():
    run = types.SimpleNamespace(trace=trace_parts.reduce(_synthetic()))
    assert device_parts.read(run, ["optimizer"]) == pytest.approx(1e3 * 140e-9 / 2)
    assert device_parts.read(run, ["kda_parts"], pass_="backward") == pytest.approx(1e3 * 100e-9 / 2)
    assert device_parts.read(run, ["kda_parts"], pass_="forward") == 0.0
    assert device_parts.read(run, ["unnamed"], as_share=True) == pytest.approx(100 * 100 / 640)
    assert device_parts.read(types.SimpleNamespace(trace=None), ["optimizer"]) is None
    run.trace["part_seconds"] = None
    assert device_parts.read(run, ["optimizer"]) is None


@pytest.fixture(scope="module")
def recorded():
    """650 ms of a traced run of the dense cell around a report (a TPU v5e),
    cut by ``trace_parts.sample``."""
    with open(os.path.join(ROOT, "benchmarks", "trace_sample_parts.json")) as f:
        trace = json.load(f)
    return trace, trace_parts.reduce(trace)


def test_recorded_parts_sum_to_the_busy_union_of_the_whole_runs(recorded):
    trace, r = recorded
    assert r["step_runs"] == 2
    device = next(p for p in trace["planes"] if p["name"].startswith("/device:TPU"))
    lines = {line["name"]: line["events"] for line in device["lines"]}
    runs = [(e[1], e[1] + e[2]) for e in lines["XLA Modules"]][1:-1]
    busy = sum(e - s for lo, hi in runs for s, e in trace_reduce.union(
        trace_reduce._clip([(o[1], o[1] + o[2]) for o in lines["XLA Ops"] if lo <= o[1] and o[1] + o[2] <= hi],
                           lo, hi)))
    assert sum(f + b for f, b in r["part_seconds"].values()) == pytest.approx(busy / 1e9, rel=1e-9)
    ms = trace_parts.per_step_ms(r)
    assert sum(f + b for f, b in ms.values()) == pytest.approx(145.857, abs=0.01)
    # the dense decoder's parts, each with time in both passes but the optimizer's
    named = {k for k, (f, b) in ms.items() if f > 0 and b > 0}
    assert named >= {"embed", "attention", "flash", "mlp", "head_loss"}
    assert ms["optimizer"][0] > 0 and ms["optimizer"][1] == 0


def test_recorded_pause_is_named_by_the_fetch_and_the_clocks_agree(recorded):
    _, r = recorded
    assert dict(r["idle_gaps"]).keys() == {"katib:fetch"}
    assert 0 <= r["dispatch_lead_us"] < 2000


def test_device_parts_reads_the_recorded_trace(recorded):
    run = types.SimpleNamespace(trace=recorded[1])
    assert device_parts.read(run, ["optimizer"]) == pytest.approx(5.65, abs=0.01)
    assert device_parts.read(run, ["mlp"], pass_="backward") == pytest.approx(47.39, abs=0.01)
    assert device_parts.read(run, ["unnamed"], as_share=True) == pytest.approx(1.69, abs=0.01)
