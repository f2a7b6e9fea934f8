"""Every file the benchmark is driven by loads, and the names agree: a cell, a
configuration or a per-layer metric is its own file plus an entry in
BENCHMARK.json, and nothing else has to change."""

import importlib
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path.insert(0, BENCH)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _names(folder):
    return sorted(n[:-5] for n in os.listdir(os.path.join(BENCH, folder)) if n.endswith(".json"))


BENCHMARK = _load(ROOT, "BENCHMARK.json")
CELLS = {w["name"]: w for w in BENCHMARK["workloads"]}
END_TO_END = {m["name"]: m for m in BENCHMARK["end_to_end"]}


def _reports(metric, cell):
    return cell in metric.get("workloads", list(CELLS))


def test_benchmark_json_has_exactly_the_contract_keys():
    assert set(BENCHMARK) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert BENCHMARK["command"] == ["python3", "benchmarks/run.py"]
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    assert "setup_s" in END_TO_END and END_TO_END["setup_s"]["bound"] <= 0.1
    assert all(0.01 <= m["bound"] <= 0.1 for m in BENCHMARK["end_to_end"])
    assert len(json.dumps(BENCHMARK)) < 64 * 1024
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCHMARK[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)


@pytest.mark.parametrize("entry", BENCHMARK["configs"], ids=lambda e: e["name"])
def test_configuration_file_states_what_is_run(entry):
    config = _load(ROOT, entry["file"])
    assert entry["file"] == f"benchmarks/configs/{entry['name']}.json"
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    assert config["reduced"] == entry["reduced"]
    # no width is ever cut
    assert not any(
        k.endswith(("_dim", "_rank", "_size")) or "head" in k for k in entry["reduced"])
    for key in entry["reduced"]:
        assert config["published"][key] != config[key]
    assert any(w["config"] == entry["name"] for w in BENCHMARK["workloads"])


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_file_agrees_with_benchmark_json(cell):
    from experiment import load_cell

    spec, config = load_cell(cell)
    entry = CELLS[cell]
    assert spec["config"] == entry["config"] and spec["chips"] == entry["chips"] == 1
    assert spec["why"] == entry["why"] and len(entry["why"]) <= 200
    assert cell == f"{entry['config']}.{entry['traffic']}"
    assert set(spec["window"]) == {"opens_after", "closes_on"}
    assert set(spec["limits"]) == {"loss_gap", "grad_norm_gap", "delta_norm_gap"}
    assert all(isinstance(v, float) and 0 < v < 1 for v in spec["limits"].values())
    reported = [m for m in BENCHMARK["end_to_end"] if _reports(m, cell)]
    assert "setup_s" in [m["name"] for m in reported] and len(reported) >= 2
    assert any(_reports(m, cell) for m in BENCHMARK["per_layer"])


@pytest.mark.parametrize("metric", _names("layer_metrics"))
def test_layer_metric_file_names_what_exists(metric):
    import metrics

    spec = _load(BENCH, "layer_metrics", f"{metric}.json")
    reader = importlib.import_module(f"readers.{spec['reader']}")
    assert callable(reader.read)
    assert spec["moves"] in metrics.END_TO_END and spec["better"] in ("lower", "higher")
    assert set(spec["workloads"]) <= set(_names("workloads"))
    entry = next((m for m in BENCHMARK["per_layer"] if m["name"] == metric), None)
    if entry is None:  # a metric of a cell that BENCHMARK.json does not list (yet)
        assert not set(spec["workloads"]) & set(CELLS)
        return
    for key in ("unit", "better", "source", "layer", "moves", "workloads"):
        assert spec[key] == entry[key]
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    moved = END_TO_END[spec["moves"]]
    for cell in spec["workloads"]:
        assert cell in CELLS
        assert _reports(moved, cell), f"{cell} does not report {spec['moves']}"
    if "roofline" in metric or "mfu" in metric:
        assert spec["unit"] == "%"


def test_every_per_layer_entry_has_its_file_and_every_cell_its_files():
    # files of a cell that is not listed (yet) may be there; an entry without its file may not
    assert {m["name"] for m in BENCHMARK["per_layer"]} <= set(_names("layer_metrics"))
    assert set(CELLS) <= set(_names("workloads"))
    assert {c["name"] for c in BENCHMARK["configs"]} <= set(_names("configs"))
    # a kernel's roofline that moves a metric stands beside the whole step's mfu
    for m in BENCHMARK["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                       and set(m["workloads"]) <= set(o["workloads"])
                       for o in BENCHMARK["per_layer"])


def test_run_py_holds_no_cell_and_no_size():
    with open(os.path.join(BENCH, "run.py")) as f:
        text = f.read()
    for word in list(CELLS) + [c["name"] for c in BENCHMARK["configs"]] + ["2048", "50304", "4096"]:
        assert word not in text


def test_peaks_table_is_keyed_by_device_kind():
    import run

    peaks = run.load_peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(run.Refused):
        run.load_peaks("TPU v9 imaginary")
    with pytest.raises(run.Refused):
        run.load_peaks("source")
