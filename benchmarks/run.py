#!/usr/bin/env python3
"""The benchmark: one cell, one run.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process; it never sets JAX_PLATFORMS. It builds the experiment a user would
write from the cell's files (workloads/, configs/), starts it through
``ExperimentController`` with the defaults a user gets — suggester, scheduler,
in-process executor, ``run_lm_trial``, ``ctx.report``, observation store — and
watches it through tees.py. The cell's ``window`` rule (window.py) says when the
measured window opens and on what boundary it closes; everything before it is
set-up. After the window the experiment is stopped, the device's peak memory is
read, the program's state is let go, and only then the plain reference
(reference_lm.py) follows the first steps of the trials that ran, for
``correct`` (check.py).

The last line of standard output is the result; everything else goes to
standard error. No TPU, a device that peaks.json does not know, or fewer chips
than the cell asks for: non-zero exit, no result line.

This file holds no cell's name and no model's size: cells, configurations and
per-layer metrics are files found by the names in BENCHMARK.json.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse
import gc
import importlib
import json
import os
import random
import shutil
import sys
import threading
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

OUT_DIR = os.path.join(ROOT, ".bench_out")
OPEN_TIMEOUT_S = 900.0     # set-up that has not opened the window by then has failed
UNWIND_TIMEOUT_S = 180.0   # a killed trial unwinds at its next report


class Refused(Exception):
    """The run cannot be made here; no result line."""


def log(*args: Any) -> None:
    print(*args, file=sys.stderr, flush=True)


def find_device(chips: int) -> Dict[str, Any]:
    """The device as JAX reports it, or Refused: the benchmark measures a TPU
    that peaks.json knows, and nothing else."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices)}
    if d0.platform != "tpu":
        raise Refused(f"JAX found no TPU: platform is {d0.platform!r}")
    if len(jax.local_devices()) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX found {len(jax.local_devices())}")
    return device


def load_peaks(kind: str) -> Dict[str, float]:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table or not isinstance(table[kind], dict):
        raise Refused(f"device kind {kind!r} is not in benchmarks/peaks.json")
    return table[kind]


def load_benchmark() -> Dict[str, Any]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def layer_metric_files() -> Dict[str, Dict[str, Any]]:
    folder = os.path.join(HERE, "layer_metrics")
    out = {}
    for name in sorted(os.listdir(folder)):
        if name.endswith(".json"):
            with open(os.path.join(folder, name)) as f:
                out[name[: -len(".json")]] = json.load(f)
    return out


def peak_memory_bytes() -> int:
    import jax

    return max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in jax.local_devices()
    )


def read_spans(root: str, experiment: str) -> Dict[str, List[Dict[str, Any]]]:
    """The program's persisted Tracer spans, one file per finished trial."""
    folder = os.path.join(root, "traces", experiment)
    spans = {}
    if os.path.isdir(folder):
        for name in os.listdir(folder):
            if name.endswith(".json"):
                with open(os.path.join(folder, name)) as f:
                    data = json.load(f)
                spans[data["trial"]] = data["spans"]
    return spans


def traced(tees, cell: Dict[str, Any], deadline: float, log_dir: str) -> Optional[Dict[str, Any]]:
    """A profiler trace inside the window: ``{"seconds": s}`` of it, or
    ``{"until": "trial"}``, up to the next trial's terminal condition."""
    import jax

    import trace_reduce

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    shutil.rmtree(log_dir, ignore_errors=True)
    rule = cell["trace"]
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_EVENT):
            if "seconds" in rule:
                time.sleep(max(0.0, min(rule["seconds"], deadline - time.time())))
            else:
                seen = len(tees.terminals)
                with tees.changed:
                    while len(tees.terminals) == seen and time.time() < deadline:
                        tees.changed.wait(min(0.5, max(0.0, deadline - time.time())))
    finally:
        jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(log_dir)
    reduced = trace_reduce.reduce(trace_reduce.load(path)) if path else None
    keep = os.environ.get("BENCH_KEEP_TRACE")  # a builder's look at a trace, by hand
    if keep and path:
        os.makedirs(os.path.dirname(keep) or ".", exist_ok=True)
        shutil.copy(path, keep)
    shutil.rmtree(log_dir, ignore_errors=True)
    return reduced


def drive(args, cell, config) -> Dict[str, Any]:
    """Start the experiment, hold the window, stop it. Returns what was seen."""
    import jax

    from katib_tpu.api.spec import experiment_spec_from_mapping
    from katib_tpu.controller.experiment import ExperimentController
    from katib_tpu.utils.compilation import enable_compilation_cache

    import tees as tees_module
    import window as window_module
    from experiment import experiment_document

    cache_dir = enable_compilation_cache()  # before the first compile of the process
    if not cell.get("persistent_compile_cache", True):
        # the cell's traffic is programs no process has compiled before
        jax.config.update("jax_enable_compilation_cache", False)
        cache_dir = None
    meter = tees_module.CompileMeter()
    tees = tees_module.Tees()
    root = os.path.join(OUT_DIR, args.workload, f"seed-{args.seed}")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    name = "bench"
    rule = cell["window"]
    failure: List[Exception] = []
    ctrl = ExperimentController(root_dir=root, devices=jax.local_devices()[: cell["chips"]])
    closed = False
    try:
        ctrl.create_experiment(
            experiment_spec_from_mapping(experiment_document(name, cell, config, args.seed))
        )

        def run() -> None:
            try:
                ctrl.run(name)  # a user's defaults: poll_interval 0.5 s
            except Exception as e:  # reported by the main thread
                failure.append(e)
                with tees.changed:
                    tees.changed.notify_all()

        runner = threading.Thread(target=run, name="bench-run", daemon=True)
        runner.start()

        def events():
            return [(r[0], r[3]) for r in tees.all_reports()], list(tees.terminals)

        give_up = time.time() + OPEN_TIMEOUT_S
        with tees.changed:
            while True:
                # events arrive under this lock, so none slips in between the two looks
                t_open = window_module.open_time(rule, *events())
                if t_open is not None or failure or time.time() > give_up or not runner.is_alive():
                    break
                tees.changed.wait(0.5)
        if t_open is None:
            raise RuntimeError(
                f"the window never opened: {failure[0]!r}" if failure else
                "the window never opened: the experiment ended or set-up took too long"
            )
        deadline = t_open + args.seconds
        trace = None
        if args.trace:
            trace = traced(tees, cell, deadline, os.path.join(root, "profile"))
        time.sleep(max(0.0, deadline - time.time()) + 0.05)
        memory_peak = peak_memory_bytes()
        reports, terminals = events()
        assignments = [t.assignments_dict() for t in ctrl.state.list_trials(name)]
        ctrl.close()
        closed = True
        runner.join(timeout=30.0)
        if not tees.wait_idle(UNWIND_TIMEOUT_S):
            raise RuntimeError("a trial function is still running after the experiment was closed")
    finally:
        if not closed:
            ctrl.close()
            tees.wait_idle(UNWIND_TIMEOUT_S)
        tees.close()
    return {
        "root": root, "experiment": name, "cache_dir": cache_dir, "tees": tees,
        "window": window_module.measure(rule, args.seconds, reports, terminals),
        "reports": reports, "terminals": terminals, "compiles": list(meter.compiles),
        "cache": {"hits": meter.cache_hits, "misses": meter.cache_misses},
        "memory_peak": memory_peak, "trace": trace, "assignments": assignments,
    }


def decide_correct(args, cell, config, seen) -> Dict[str, List[float]]:
    """Every number compared, beside its limit. Runs once the window is closed,
    the peak is read and no trial holds state on the device."""
    import check
    import reference_lm
    from experiment import fixed_assignments
    from katib_tpu.db.store import obs_db_path, open_store

    window, tees = seen["window"], seen["tees"]
    checks: Dict[str, List[float]] = {}

    # report path: rows on disk against what the trials handed in up to the close
    store = open_store(obs_db_path(seen["root"]))
    try:
        teed, stored = {}, {}
        for rec in tees.records:
            values = [float(m["loss"]) for t, _, m in rec.reports if t <= window.t_close]
            if values:
                teed[rec.name] = values
                stored[rec.name] = [
                    float(r.value) for r in store.get_observation_log(rec.name, metric_name="loss")
                ]
    finally:
        store.close()
    checks.update(check.compare_reports(teed, stored))

    # suggester: every assignment inside the feasible space
    checks.update(check.compare_assignments(
        seen["assignments"], cell["search_space"], fixed_assignments(cell, config)))

    # model step: the trials the window drove (their first steps are read by
    # tees.py whenever they ran), a sample drawn from the seed where there are
    # more than the cell compares
    inside = [r for r in tees.records if r.delta_norm is not None and (
        r.name in window.trials or any(window.t_open <= t <= window.t_close for t, _, _ in r.reports))]
    if len(inside) > cell["check_trials"]:
        inside = random.Random(args.seed).sample(inside, cell["check_trials"])
    checks["trials_compared_too_few"] = [float(not inside), 0.0]
    if not inside:
        return checks
    shape = reference_lm.LMShape(
        config["vocab_size"], config["hidden_size"], config["num_hidden_layers"],
        config["num_attention_heads"],
    )
    wrong_shape = sum(r.batch_shape != (cell["batch_size"], cell["seq_len"]) for r in inside)
    checks["trials_at_another_size"] = [float(wrong_shape), 0.0]
    if not cell.get("persistent_compile_cache", True):
        # the window is closed: the reference's own compile may be kept
        import jax
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    reference = reference_lm.Reference(shape, cell["batch_size"], cell["seq_len"])
    t0 = time.time()
    program = [
        {"loss": r.first_losses, "grad_norm": r.grad_norm, "delta_norm": r.delta_norm}
        for r in inside
    ]
    references = [reference.run(r.learning_rate, steps=len(r.first_losses)) for r in inside]
    log(f"reference: {len(inside)} trial(s) followed in {time.time() - t0:.1f} s")
    checks.update(check.compare_training(program, references, cell["limits"]))
    return checks


def diagnostics(run, seen) -> Dict[str, Any]:
    """For a builder's eyes (the driver ignores the key): the window's counts,
    each trial cycle inside it, and the parts of each trial by the program's spans."""
    window = run.window
    ends = [window.t_open] + [t for t, _, _ in run.terminals if window.t_open < t <= window.t_close]
    parts = []
    for trial in window.trials:
        spans = {s["name"]: s["end"] - s["start"] for s in run.spans.get(trial, []) if s.get("end")}
        root = next((s for s in run.spans.get(trial, []) if s["name"] == "trial"), None)
        parts.append(dict(
            {k: spans.get(k) for k in ("trial", "suggestion", "compile", "steps", "finalize")},
            backend_compile=[round(sec, 3) for t, sec in run.compiles
                             if root and root["start"] <= t <= root["end"]]))
    return {
        "trial_parts": parts, "setup_s": window.t_open - run.t_start, "seconds": window.seconds,
        "steps": window.steps, "reports": window.reports, "trials": len(window.trials),
        "trial_cycles_s": [b - a for a, b in zip(ends, ends[1:])], "compile_cache": seen["cache"],
        "compiles": len(run.compiles), "cache_dir": seen["cache_dir"],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import metrics as metrics_module
    from experiment import load_cell
    from rundata import RunData

    try:
        cell, config = load_cell(args.workload)
        device = find_device(cell["chips"])
        peaks = load_peaks(device["kind"])
    except Refused as e:
        log(f"refused: {e}")
        return 2
    bench = load_benchmark()
    listed = any(w["name"] == args.workload for w in bench["workloads"])

    seen = drive(args, cell, config)
    if args.trace and seen["trace"] is None:
        log("the trace holds no operation on a device")
        return 3
    window = seen["window"]
    run = RunData(
        cell=cell, config=config, peaks=peaks, t_start=T_START, window=window,
        reports=seen["reports"], terminals=seen["terminals"], compiles=seen["compiles"],
        spans=read_spans(seen["root"], seen["experiment"]), trace=seen["trace"],
    )

    # a listed cell reports what BENCHMARK.json says; a cell that is not listed
    # yet (the tests' tiny ones, a cell a later PR lists) every metric that finds
    # something to read
    wanted = [m["name"] for m in bench["end_to_end"]
              if args.workload in m.get("workloads", [args.workload])] if listed else list(
                  metrics_module.END_TO_END)
    end_to_end = {
        name: (metrics_module.END_TO_END[name][0](run), metrics_module.END_TO_END[name][1])
        for name in wanted
    }
    values: Dict[str, Any] = {}
    if args.trace:
        for name, spec in layer_metric_files().items():
            if (args.workload in spec["workloads"]) if listed else (
                    end_to_end.get(spec["moves"], (None,))[0] is not None):
                reader = importlib.import_module(f"readers.{spec['reader']}")
                values[name] = (reader.read(run, **spec.get("args", {})), spec["unit"])
    else:
        values = end_to_end
    out_metrics = {
        k: {"value": v, "unit": unit} for k, (v, unit) in values.items() if v is not None
    }

    closes_on_trial = cell["window"]["closes_on"] == "trial"
    attempted = len(window.trials) if closes_on_trial else window.reports
    failed = len(window.failed) if closes_on_trial else 0
    if attempted == 0:  # nothing finished inside the window: a failed run, not a zero
        attempted, failed = 1, 1

    gc.collect()
    checks = decide_correct(args, cell, config, seen)
    import check

    correct = check.verdict(checks) and failed == 0
    device = dict(device, memory_peak_bytes=seen["memory_peak"])
    result: Dict[str, Any] = {
        "correct": bool(correct), "attempted": attempted, "failed": failed,
        "metrics": out_metrics, "device": device,
    }
    if seen["trace"] is not None:
        device.update(busy_s=seen["trace"]["busy_s"], window_s=seen["trace"]["window_s"])
        result["breakdown"] = {
            "device_ops": seen["trace"]["device_ops"], "idle_gaps": seen["trace"]["idle_gaps"],
        }
    result["window"] = diagnostics(run, seen)
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    shutil.rmtree(seen["root"], ignore_errors=True)
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v!r} (limit {lim!r}){'' if v <= lim else '  <-- over'}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
