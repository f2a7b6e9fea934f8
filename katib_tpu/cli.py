"""katib-tpu CLI — submit/inspect experiments from the terminal.

Terminal-first replacement for the reference's Web-UI backend REST surface
(cmd/ui/v1beta1/main.go:42-75: fetch_experiments, create_experiment,
fetch_hp_job_info, fetch_trial_logs). Subcommands:

  run <spec.{json,yaml}>   create an experiment from a JSON/YAML spec (plain
                           or Katib CRD envelope) and drive it
  resume <name>            resume a persisted experiment in a fresh controller
  list                     list experiments in a state root
  status <name>            experiment status + trial buckets + optimal trial
  trials <name>            per-trial table (the fetch_hp_job_info view)
  queue                    fair-share scheduler queue (pending trials with
                           priority, wait, deficit; --url asks a live
                           controller's /api/queue, else persisted state)
  importance <name>        correlation-based parameter-importance table
  trace <experiment> <trial>  indented lifecycle span tree with durations and
                           % of trial wall-clock (--url asks a live
                           controller; else the persisted trace under
                           <root>/traces/)
  top                      per-trial resource table (RSS / CPU / HBM / time
                           since last report; --url asks a live controller's
                           /api/telemetry, --watch refreshes; else renders
                           the persisted series under <root>/telemetry/)
  compile                  AOT compile service registry (fingerprint, state,
                           cost estimate, compile time, trials served; --url
                           asks a live controller's /api/compile, else reads
                           the snapshot under <root>/compilesvc/)
  rungs <experiment>       multi-fidelity ladder view (per-rung population,
                           running/paused/promoted/pruned counts and best
                           objective), offline from the state root
  metrics <trial>          raw observation log for one trial
  recover <experiment>     offline crash-recovery inspection: the state
                           root's single-writer lease, the recovery
                           journal's tail, and the in-flight trials a
                           checkpoint-preserving restart would requeue
  replicas                 sharded-control-plane placement table: live
                           replica registrations and per-experiment
                           placement leases (owner, fence, heartbeat age),
                           offline from <root>/placement/
  algorithms               registered suggestion / early-stopping algorithms
  check [paths]            recompile-hazard / lock-discipline / repo-invariant
                           static analysis (docs/static-analysis.md); exits 1
                           on non-suppressed findings
  analyze <spec|module:fn> semantic program analysis: compile fingerprint,
                           shape-affecting vs runtime-scalar parameter
                           classification, FLOPs/HBM cost table, KTX4xx
                           findings (jaxpr-level, never executes the trial)
  ui                       serve the web dashboard + REST API
  serve                    run the suggestion/early-stopping/db-manager service

Experiments with in-process entry points use trialTemplate.entryPoint
("module:function"); arbitrary subprocess commands work via
trialTemplate.command exactly like Katib YAML trial templates.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Optional


def _local_accelerators():
    """The host's real accelerator devices, or None on a CPU host.

    Whether an accelerator is expected is read from config/env first, so a
    CPU-held process never initializes a backend here. On a host that has a
    chip the probe raises if the chip cannot be reached: pooling abstract
    slots there would let every trial land on chip 0 unseen."""
    from .utils.backend import bounded_local_devices
    from .utils.compilation import accelerator_expected

    if not accelerator_expected():
        return None
    devices = bounded_local_devices()
    if not devices or devices[0].platform == "cpu":
        return None
    return list(devices)


def _controller(
    root: Optional[str],
    devices: Optional[int] = None,
    readonly: bool = False,
    in_process_trials: bool = False,
):
    """``devices`` (the --devices count) sizes a pool of abstract slots.
    Without it, a controller whose trials run in its own process pools the
    host's real accelerator devices when it has any; a controller of
    subprocess trials stays off the backend (its children need the chip)
    and keeps the scheduler's default abstract slots."""
    from .controller.experiment import ExperimentController

    devs = None
    if devices:
        devs = list(range(devices))
    elif in_process_trials and not readonly:
        devs = _local_accelerators()
    config = None
    if readonly:
        # inspection commands must not contend the running controller's
        # single-writer lease (controller/recovery.py) — they only read
        # persisted state, so the recovery subsystem stays off
        from .config import load_config

        config = load_config()
        config.runtime.recovery = False
    return ExperimentController(root_dir=root, devices=devs, config=config)


def cmd_run(args) -> int:
    from .api.spec import load_experiment_document
    from .api.validation import ValidationError

    # JSON or YAML, plain spec or the reference's CRD envelope
    # (apiVersion/kind/metadata/spec — the kubectl-apply shape every
    # reference examples/v1beta1 file uses)
    with open(args.spec) as f:
        try:
            spec = load_experiment_document(f.read())
        except (ValueError, KeyError, TypeError) as e:
            # KeyError/TypeError: parseable document, malformed spec shape
            # (e.g. a parameter entry missing 'name') — still a user error,
            # still the friendly message + rc=2, not a traceback
            print(f"invalid experiment spec: {type(e).__name__}: {e}", file=sys.stderr)
            return 2
    ctrl = _controller(
        args.root, args.devices,
        in_process_trials=spec.trial_template.command is None,
    )
    try:
        ctrl.create_experiment(spec)
    except (ValidationError, ValueError) as e:
        print(f"invalid experiment spec: {e}", file=sys.stderr)
        return 2
    print(f"experiment {spec.name} created; running...")
    exp = ctrl.run(spec.name, timeout=args.timeout)
    _print_status(exp)
    ctrl.close()
    return 0 if exp.status.is_succeeded else 1


def cmd_resume(args) -> int:
    """Resume a persisted (FromVolume-style) experiment in a fresh process:
    restore state, requeue in-flight trials, drive to completion."""
    import os

    from .db.state import ExperimentStateStore

    # same pool as `run` would build: peek at the persisted template first
    persisted = ExperimentStateStore(os.path.join(args.root, "state")).load(args.name)
    ctrl = _controller(
        args.root, args.devices,
        in_process_trials=(
            persisted is not None
            and persisted.spec.trial_template.command is None
        ),
    )
    try:
        try:
            ctrl.load_experiment(args.name)
        except KeyError as e:
            print(str(e), file=sys.stderr)
            return 1
        print(f"experiment {args.name} restored; resuming...")
        exp = ctrl.run(args.name, timeout=args.timeout)
        _print_status(exp)
        return 0 if exp.status.is_succeeded else 1
    finally:
        ctrl.close()


def cmd_list(args) -> int:
    ctrl = _controller(args.root, readonly=True)
    _load_all(ctrl, args.root)
    rows = [
        (e.name, e.status.condition.value, e.status.reason.value,
         f"{e.status.trials_succeeded}/{e.status.trials}")
        for e in ctrl.state.list_experiments()
    ]
    _table(["NAME", "STATUS", "REASON", "SUCCEEDED/TOTAL"], rows)
    return 0


def cmd_status(args) -> int:
    ctrl = _controller(args.root, readonly=True)
    _load_all(ctrl, args.root)
    exp = ctrl.state.get_experiment(args.name)
    if exp is None:
        print(f"experiment {args.name!r} not found", file=sys.stderr)
        return 1
    _print_status(exp)
    return 0


def cmd_trials(args) -> int:
    ctrl = _controller(args.root, readonly=True)
    _load_all(ctrl, args.root)
    trials = ctrl.state.list_trials(args.name)
    rows = []
    for t in trials:
        metric = ""
        if t.observation and t.observation.metrics:
            m = t.observation.metrics[0]
            metric = f"{m.name}={m.latest}"
        rows.append((t.name, t.condition.value, t.current_reason,
                     json.dumps(t.assignments_dict()), metric))
    _table(["TRIAL", "STATUS", "REASON", "ASSIGNMENTS", "METRIC"], rows)
    return 0


def cmd_queue(args) -> int:
    """Fair-share queue state (ISSUE 2 satellite): live from a running
    controller's /api/queue when --url is given; otherwise reconstructed
    from persisted state (pending trials + priorities from the spec, wait
    from the Pending condition timestamp — live-only fields like the
    fair-share deficit are then unavailable)."""
    if args.url:
        import urllib.request

        with urllib.request.urlopen(args.url.rstrip("/") + "/api/queue") as r:
            state = json.loads(r.read().decode())
        d = state.get("devices", {})
        print(
            f"devices:   {d.get('free', '?')}/{d.get('total', '?')} free"
            + (f" ({d.get('quarantined')} quarantined)" if d.get("quarantined") else "")
        )
        rows = [
            (p["trial"], p["experiment"], p["priorityClass"],
             f"{p['effectivePriority']:.2f}", f"{p['waitSeconds']:.1f}s",
             str(p["numDevices"]),
             "-" if p.get("deviceQuota") is None else str(p["deviceQuota"]),
             f"{p['fairShareDeficit']:.2f}")
            for p in state.get("pending", [])
        ]
        _table(
            ["TRIAL", "EXPERIMENT", "CLASS", "EFF-PRIO", "WAIT", "DEVICES",
             "QUOTA", "DEFICIT"],
            rows,
        )
        running = state.get("running", [])
        if running:
            print()
            _table(
                ["RUNNING UNIT", "EXPERIMENT", "TRIALS", "DEVICES", "PRIO",
                 "PREEMPTING", "ELAPSED"],
                [
                    (u["unit"], u["experiment"], str(len(u["trials"])),
                     str(u["devices"]), str(u["priority"]),
                     "yes" if u["preempting"] else "no",
                     f"{u['runningSeconds']:.1f}s")
                    for u in running
                ],
            )
        return 0

    import time as _time

    from .api.status import TrialCondition
    from .controller.fairshare import priority_of

    ctrl = _controller(args.root, readonly=True)
    _load_all(ctrl, args.root)
    now = _time.time()
    rows = []
    for exp in ctrl.state.list_experiments():
        for t in ctrl.state.list_trials(exp.name):
            if t.condition != TrialCondition.PENDING:
                continue
            pending_since = next(
                (c.last_transition_time for c in t.conditions
                 if c.type == TrialCondition.PENDING.value),
                None,
            )
            wait = f"{now - pending_since:.1f}s" if pending_since else "-"
            rows.append(
                (t.name, exp.name, exp.spec.priority_class or "default",
                 str(priority_of(exp)), wait,
                 str(max(exp.spec.trial_template.resources.num_devices, 1)),
                 t.current_reason or "-")
            )
    _table(
        ["TRIAL", "EXPERIMENT", "CLASS", "PRIO", "WAIT", "DEVICES", "REASON"],
        rows,
    )
    if not rows:
        print("(queue empty; use --url http://host:port for a live "
              "controller's /api/queue view)")
    return 0


def cmd_importance(args) -> int:
    from .ui.server import parameter_importance

    ctrl = _controller(args.root, readonly=True)
    _load_all(ctrl, args.root)
    exp = ctrl.state.get_experiment(args.name)
    if exp is None:
        print(f"experiment {args.name!r} not found", file=sys.stderr)
        return 1
    out = parameter_importance(exp, ctrl.state.list_trials(args.name))
    if not out["importance"]:
        if out["n"] < 3:
            print(f"no importance available ({out['n']} completed rankable trials; need >= 3)")
        else:
            print(f"no importance available: none of the parameters were scorable "
                  f"over the {out['n']} completed trials (non-numeric or "
                  "constant values)")
        return 0
    rows = [
        (r["parameter"], f"{r['importance']:.4f}", r["method"], str(r["n"]))
        for r in out["importance"]
    ]
    _table(["PARAMETER", "IMPORTANCE", "METHOD", "N"], rows)
    print(f"(correlation-based screen over {out['n']} completed trials, "
          "not a causal claim)")
    return 0


def _write_perfetto(spans, out_path: str, label: str) -> int:
    """Dump spans as a Chrome trace_event file (openable in
    ui.perfetto.dev) — tmp + os.replace, the repo persistence idiom."""
    import os

    from .tracing import to_perfetto

    doc = to_perfetto(spans, trace_name=f"katib-tpu {label}")
    tmp = f"{out_path}.tmp{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, out_path)
    print(f"wrote {len(spans)} spans to {out_path} (open in ui.perfetto.dev)")
    return 0


def cmd_trace(args) -> int:
    """Trial lifecycle span tree (ISSUE 4 tentpole): where did this trial's
    wall-clock go — queue wait, compile, steps, checkpointing, flush
    barriers, preemption. Live from a running controller's trace endpoint
    when --url is given; otherwise from the trace persisted at trial end,
    merged with any cross-replica spans under <root>/traces/wire/ (the
    distributed plane, ISSUE 19). Omit the trial for the experiment-level
    view: every trial's trace, worst-first by root-span duration."""
    import os

    from .tracing import Span, experiment_traces, merge_trace, render_tree

    if args.trial is None:
        if args.url:
            print(
                "experiment-level traces are read offline from --root; "
                "drop --url (per-trial live traces still take --url)",
                file=sys.stderr,
            )
            return 1
        traces = experiment_traces(args.root, args.experiment)
        if not traces:
            print(
                f"no traces for experiment {args.experiment!r} under "
                f"{args.root}/traces (did it run with tracing on?)",
                file=sys.stderr,
            )
            return 1
        rows = []
        for t in traces:
            dur = t.get("rootDurationSeconds")
            # the deep-profile linkage (ISSUE 20): the trial root span is
            # stamped with the xplane dump dir when profiling dumps survived
            profile = "-"
            for s in t.get("spans", []):
                if s.get("parentId") is None:
                    profile = (s.get("attrs") or {}).get("profileDir") or "-"
                    break
            rows.append((
                t.get("trial") or "?",
                (t.get("traceId") or "?")[:16],
                f"{dur:.3f}" if dur is not None else "-",
                len(t.get("spans", [])),
                ",".join(t.get("replicas") or []) or "-",
                profile,
            ))
        _table(
            ["TRIAL", "TRACE", "ROOT-SECONDS", "SPANS", "REPLICAS", "PROFILE"],
            rows,
        )
        all_spans = [
            Span.from_dict(s) for t in traces for s in t.get("spans", [])
        ]
        if args.format == "perfetto":
            out = args.output or f"{args.experiment}.perfetto.json"
            return _write_perfetto(all_spans, out, args.experiment)
        for t in traces:
            spans = [Span.from_dict(s) for s in t.get("spans", [])]
            print()
            print(f"{t.get('trial') or '?'} — trace {t.get('traceId', '?')} "
                  f"({len(spans)} spans)")
            print(render_tree(spans))
        return 0
    if args.url:
        import urllib.error
        import urllib.request

        url = (
            args.url.rstrip("/")
            + f"/api/experiments/{args.experiment}/trials/{args.trial}/trace"
        )
        try:
            with urllib.request.urlopen(url) as r:
                trace = json.loads(r.read().decode())
        except urllib.error.HTTPError as e:
            print(f"no trace: HTTP {e.code} from {url}", file=sys.stderr)
            return 1
    else:
        path = os.path.join(args.root, "traces", args.experiment, f"{args.trial}.json")
        if not os.path.exists(path):
            print(
                f"no persisted trace at {path} (did the trial run with "
                "tracing on and a --root?); use --url for a live controller",
                file=sys.stderr,
            )
            return 1
        with open(path) as f:
            trace = json.load(f)
        trace = merge_trace(args.root, trace)
    spans = [Span.from_dict(s) for s in trace.get("spans", [])]
    label = f"{args.experiment}/{args.trial}"
    if args.format == "perfetto":
        out = args.output or f"{args.experiment}_{args.trial}.perfetto.json"
        return _write_perfetto(spans, out, label)
    replicas = ",".join(trace.get("replicas") or [])
    print(f"trace {trace.get('traceId', '?')} — {label} ({len(spans)} spans"
          + (f", replicas: {replicas}" if replicas else "") + ")")
    print(render_tree(spans))
    return 0


def cmd_fleet(args) -> int:
    """Fleet status plane (ISSUE 19): one table over every REGISTERED
    replica — liveness, claims, failovers, rpc/ingest counters and
    per-tenant SLO standing — by fanning out to the live replicas'
    /metrics and status endpoints from the placement registry. Dead
    replicas stay visible (alive=no): a fleet view that hides the corpse
    hides the incident."""
    import time as _time

    from .service.httpapi import fleet_snapshot

    while True:
        snap = fleet_snapshot(args.root, token=args.token)
        rows = []
        for r in snap["replicas"]:
            m = r.get("metrics") or {}
            slo = m.get("sloViolations") or {}
            depth = m.get("ingestCoalesceDepth")
            rows.append((
                r.get("replica") or "?",
                "up" if r.get("alive") else "DOWN",
                r.get("pid") if r.get("pid") is not None else "-",
                len(r.get("claimed") or []),
                r.get("capacity") if r.get("capacity") is not None else "-",
                r.get("failovers") if r.get("failovers") is not None else "-",
                int(m["rpcRequests"]) if "rpcRequests" in m else "-",
                int(m["ingestFrames"]) if "ingestFrames" in m else "-",
                f"{depth:g}" if depth is not None else "-",
                int(sum(slo.values())) if slo else ("-" if not m else 0),
            ))
        _table(
            ["REPLICA", "STATE", "PID", "CLAIMED", "CAP", "FAILOVERS",
             "RPCS", "FRAMES", "DEPTH", "SLO-VIOL"],
            rows,
        )
        if not rows:
            print(
                f"(no replicas registered under {args.root}/placement/"
                "replicas — is this the shared state root?)"
            )
        # step-performance rollups (ISSUE 20): one row per (replica,
        # experiment) with perf gauges — present only when the step-stats
        # knob was on somewhere in the fleet
        perf_rows = []
        for r in snap["replicas"]:
            for exp, p in ((r.get("metrics") or {}).get("perf") or {}).items():
                p95 = p.get("p95")
                thr = p.get("throughput")
                mfu_v = p.get("mfu")
                perf_rows.append((
                    r.get("replica") or "?",
                    exp,
                    f"{p95:.4f}" if p95 is not None else "-",
                    f"{thr:.2f}" if thr is not None else "-",
                    f"{mfu_v:.3f}" if mfu_v is not None else "-",
                    int(p.get("retraces", 0)),
                    f"{p['objectivePerDeviceSecond']:.6g}"
                    if p.get("objectivePerDeviceSecond") is not None else "-",
                ))
        if perf_rows:
            print()
            _table(
                ["REPLICA", "EXPERIMENT", "STEP-P95", "STEPS/S", "MFU",
                 "RETRACES", "OBJ/DEV-S"],
                perf_rows,
            )
        tenants = snap.get("tenants") or []
        if tenants:
            print()
            _table(
                ["TENANT", "CLAIMED", "MAX-EXP", "ADMIT/MIN", "DEVICES",
                 "WEIGHT"],
                [
                    (
                        t["tenant"], t["claimed"],
                        t["maxExperiments"] if t["maxExperiments"] else "-",
                        t["admissionPerMinute"] if t["admissionPerMinute"] else "-",
                        t["deviceQuota"] if t["deviceQuota"] else "-",
                        t["fairShareWeight"],
                    )
                    for t in tenants
                ],
            )
        if not args.watch:
            return 0
        try:
            _time.sleep(args.interval)
        except KeyboardInterrupt:
            return 0
        print()


def cmd_perf(args) -> int:
    """Step-performance table (ISSUE 20): per-trial step timing, throughput,
    MFU and retrace counts folded offline from the persisted perf rows
    (``katib-tpu/perf/`` observation namespace). Empty unless the sweep ran
    with runtime.step_stats / KATIB_TPU_STEP_STATS on."""
    from .runtime.stepstats import summarize_perf_rows

    ctrl = _controller(args.root, readonly=True)
    _load_all(ctrl, args.root)
    exp = ctrl.state.get_experiment(args.experiment)
    if exp is None:
        print(f"experiment {args.experiment!r} not found", file=sys.stderr)
        return 1
    trials = ctrl.state.list_trials(args.experiment)
    summaries = []
    for t in trials:
        s = summarize_perf_rows(ctrl.obs_store.get_observation_log(t.name))
        if s is not None:
            summaries.append((t, s))
    if args.format == "json":
        print(json.dumps(
            {
                "experiment": args.experiment,
                "trials": [
                    dict(s, trial=t.name, status=t.condition.value)
                    for t, s in summaries
                ],
            },
            indent=2, sort_keys=True,
        ))
        return 0
    if not summaries:
        print(
            f"no step-performance rows for experiment {args.experiment!r} "
            "(run with KATIB_TPU_STEP_STATS=1 / runtime.step_stats)"
        )
        return 0

    def fmt(v, spec="{:.4f}"):
        return spec.format(v) if v is not None else "-"

    rows = [
        (
            t.name, t.condition.value, s["stints"], s["windows"],
            fmt(s["stepSecondsP50"]), fmt(s["stepSecondsP95"]),
            fmt(s["stepsPerSecond"], "{:.2f}"),
            fmt(s["examplesPerSecond"], "{:.2f}"),
            fmt(s["mfu"], "{:.3f}"), s["retraces"],
        )
        for t, s in summaries
    ]
    _table(
        ["TRIAL", "STATUS", "STINTS", "WINDOWS", "STEP-P50", "STEP-P95",
         "STEPS/S", "EXAMPLES/S", "MFU", "RETRACES"],
        rows,
    )
    return 0


def cmd_top(args) -> int:
    """Per-trial resource table (ISSUE 5 tentpole): RSS / CPU / HBM / time
    since the last metric report, plus the device pool and XLA cache. Live
    from a running controller's /api/telemetry when --url is given (add
    --watch to refresh); otherwise reconstructed from the series persisted
    under <root>/telemetry/ (last sample + peaks per finished trial)."""
    import os
    import time as _time

    from .telemetry import fmt_bytes, snapshot_from_persisted, top_rows

    def fetch():
        if args.url:
            import urllib.request

            with urllib.request.urlopen(args.url.rstrip("/") + "/api/telemetry") as r:
                return json.loads(r.read().decode())
        return snapshot_from_persisted(os.path.join(args.root, "telemetry"))

    while True:
        snap = fetch()
        devices = snap.get("devices") or []
        if devices:
            used = sum(d.get("bytesInUse") or 0 for d in devices)
            print(f"devices:   {len(devices)} | HBM in use {fmt_bytes(used)}")
        cache = snap.get("xlaCache") or {}
        if cache.get("entries"):
            print(
                f"xla-cache: {cache['entries']} entries, "
                f"{fmt_bytes(cache.get('bytes', 0))}"
            )
        rows = top_rows(snap)
        _table(
            ["TRIAL", "EXPERIMENT", "RSS", "CPU", "HBM", "LAST-REPORT", "STATE"],
            rows,
        )
        if not rows:
            print(
                "(no telemetry; point --root at a controller state dir with "
                "telemetry/, or --url at a running 'katib-tpu ui' server)"
            )
        if not args.watch:
            return 0
        try:
            _time.sleep(args.interval)
        except KeyboardInterrupt:
            return 0
        print()


def cmd_compile(args) -> int:
    """AOT compile service registry (ISSUE 8 tentpole): which programs the
    controller compiled ahead of dispatch, their fingerprint/state/cost and
    how many trials each executable served. Live from a running
    controller's /api/compile when --url is given; otherwise from the JSON
    snapshot the service persists under <root>/compilesvc/."""
    import os

    if args.url:
        import urllib.error
        import urllib.request

        url = args.url.rstrip("/") + "/api/compile"
        try:
            with urllib.request.urlopen(url) as r:
                snap = json.loads(r.read().decode())
        except urllib.error.HTTPError as e:
            print(f"no compile registry: HTTP {e.code} from {url}", file=sys.stderr)
            return 1
    else:
        from .compilesvc.service import load_persisted_registry

        snap = load_persisted_registry(os.path.join(args.root, "compilesvc"))
        if snap is None:
            print(
                f"no persisted compile registry under {args.root}/compilesvc "
                "(did the controller run with the compile service on and a "
                "--root?); use --url for a live controller",
                file=sys.stderr,
            )
            return 1
    print(
        f"compiled: {snap.get('compiled', 0)} | "
        f"hits: {snap.get('hits', 0)} | misses: {snap.get('misses', 0)} | "
        f"queued: {snap.get('queueDepth', 0)}"
    )
    rows = []
    for e in snap.get("entries", []):
        cost = e.get("costFlops") or 0
        secs = e.get("compileSeconds")
        rows.append(
            (
                e.get("fingerprint") or "-",
                e.get("state", "?"),
                e.get("experiment", "?"),
                e.get("target", "?"),
                f"{cost:.3g}" if cost else "-",
                f"{secs:.2f}s" if secs is not None else "-",
                str(e.get("trialsServed", 0)),
            )
        )
    _table(
        ["FINGERPRINT", "STATE", "EXPERIMENT", "TARGET", "COST-FLOPS",
         "COMPILE", "TRIALS"],
        rows,
    )
    if not rows:
        print("(registry empty — no analyzable experiment has been admitted)")
    return 0


def cmd_devices(args) -> int:
    """Supervised device plane state (ISSUE 12): backend + probe verdict,
    the free pool, loss/failover counters, and every lease with its holder,
    state and heartbeat age — read offline from the JSON snapshot the plane
    persists under <root>/deviceplane/ (same pattern as `katib-tpu
    compile`)."""
    import os
    import time as _time

    from .controller.deviceplane import DevicePlane

    path = os.path.join(args.root, "deviceplane", DevicePlane.STATE_FILE)
    try:
        with open(path) as f:
            snap = json.load(f)
    except (OSError, ValueError):
        print(
            f"no persisted device-plane state under {args.root}/deviceplane "
            "(did the controller run with runtime.device_plane on and a "
            "--root?)",
            file=sys.stderr,
        )
        return 1
    print(
        f"backend: {snap.get('backend', '?')} | "
        f"probe: {snap.get('probeVerdict', '?')} | "
        f"free: {snap.get('freeCount', 0)} | "
        f"lost: {snap.get('lostTotal', 0)} | "
        f"failovers: {snap.get('failovers', 0)}"
    )
    now = _time.time()
    rows = []
    for lease in snap.get("leases", []):
        hb = lease.get("lastHeartbeat") or 0
        expires = lease.get("expiresAt")
        rows.append(
            (
                str(lease.get("leaseId", "?")),
                lease.get("holder") or "-",
                lease.get("state", "?"),
                str(len(lease.get("devices", []))),
                str(len(lease.get("lost", []))),
                str(lease.get("heartbeats", 0)),
                f"{max(now - hb, 0):.0f}s ago" if hb else "-",
                f"{expires - now:+.0f}s" if expires else "-",
            )
        )
    _table(
        ["LEASE", "HOLDER", "STATE", "DEVICES", "LOST", "BEATS",
         "HEARTBEAT", "EXPIRES"],
        rows,
    )
    if not rows:
        print("(no leases recorded — nothing has been dispatched yet)")
    return 0


def cmd_population(args) -> int:
    """Fused population sweep view (ISSUE 9): per-generation best/median
    from the ``<experiment>-population`` pseudo-trial rows the fused
    executor demuxes, plus the in-flight sweep checkpoint (generations
    done / demux progress) when one is persisted under
    ``<root>/fusedpop/<experiment>/``."""
    import os

    from .db.store import open_store
    from .runtime.population import CARRY_META_FILE

    meta_path = os.path.join(
        args.root, "fusedpop", args.experiment, CARRY_META_FILE
    )
    if os.path.exists(meta_path):
        try:
            with open(meta_path) as f:
                meta = json.load(f)
            print(
                f"in-flight sweep: {meta.get('generationDone', 0)} "
                f"generation(s) computed, {meta.get('reported', 0)} of the "
                "interrupted chunk demuxed (resumes bit-identically)"
            )
        except (OSError, ValueError):
            print("in-flight sweep: checkpoint unreadable", file=sys.stderr)
    db = os.path.join(args.root, "observations.db")
    store = open_store(db if os.path.exists(db) else None)
    # rows arrive in demux order (best, median per generation); group
    # sequentially — two fast generations can share a float timestamp
    rows = []
    slot: dict = {}
    for log in store.get_observation_log(f"{args.experiment}-population"):
        if log.metric_name in slot:
            rows.append(slot)
            slot = {}
        slot[log.metric_name] = log.value
    if slot:
        rows.append(slot)
    store.close()
    table = [
        (
            str(gen),
            s.get("population-best", "-"),
            s.get("population-median", "-"),
        )
        for gen, s in enumerate(rows)
    ]
    _table(["GEN", "BEST", "MEDIAN"], table)
    if not table:
        print(
            "(no population rows — was this experiment run with the fused "
            "population driver and a --root?)"
        )
    return 0


def cmd_rungs(args) -> int:
    """Multi-fidelity ladder view (ISSUE 11 + 13): per-bracket, per-rung
    budget, population, running/paused/promoted/pruned/succeeded counts and
    best objective, rebuilt offline from the persisted trial records
    (rung/bracket labels) and the observation store — no live controller
    needed. ``--format json`` dumps the full report for scripting."""
    import os

    from .controller.multifidelity import ENGINE_ALGORITHMS, ladder_report
    from .db.state import ExperimentStateStore
    from .db.store import open_store

    state = ExperimentStateStore(os.path.join(args.root, "state"))
    exp = state.load(args.experiment)
    if exp is None:
        print(f"experiment {args.experiment!r} not found under {args.root}", file=sys.stderr)
        return 1
    if exp.spec.algorithm.algorithm_name not in ENGINE_ALGORITHMS:
        print(
            f"experiment {args.experiment!r} uses algorithm "
            f"{exp.spec.algorithm.algorithm_name!r}, not one of "
            f"{sorted(ENGINE_ALGORITHMS)} (no rung ladder)",
            file=sys.stderr,
        )
        return 1
    db = os.path.join(args.root, "observations.db")
    store = open_store(db if os.path.exists(db) else None)
    try:
        report = ladder_report(
            exp.spec, state.list_trials(args.experiment), store
        )
    finally:
        store.close()
    if getattr(args, "format", "table") == "json":
        print(json.dumps(report, indent=2, sort_keys=True))
        return 0
    print(
        f"experiment {report['experiment']}: resource={report['resource']} "
        f"eta={report['eta']}"
        + (
            f" brackets={report['n_brackets']}"
            if report["n_brackets"] > 1
            else ""
        )
    )
    for section in report["brackets"]:
        if report["n_brackets"] > 1:
            print(
                f"bracket {section['bracket']}: "
                f"min_resource={section['min_resource']} "
                f"max_resource={section['max_resource']} "
                f"({section['n_rungs']} rungs)"
            )
        rows = [
            (
                str(r["rung"]),
                r["budget"],
                str(r["population"]),
                str(r["running"]),
                str(r["paused"]),
                str(r["promoted"]),
                str(r["pruned"]),
                str(r["succeeded"]),
                "-" if r["best"] is None else f"{r['best']:.6g}",
            )
            for r in section["rungs"]
        ]
        _table(
            ["RUNG", "BUDGET", "POPULATION", "RUNNING", "PAUSED", "PROMOTED",
             "PRUNED", "SUCCEEDED", "BEST"],
            rows,
        )
    return 0


def cmd_recover(args) -> int:
    """Offline crash-recovery inspection (ISSUE 14): the state root's
    single-writer lease, the recovery journal's tail, and the in-flight
    trial summary a checkpoint-preserving restart would act on — all read
    straight from disk, no controller constructed (and therefore no lease
    contention with a live one)."""
    import os

    from .controller import recovery
    from .db.state import ExperimentStateStore
    from .db.store import open_store

    root = args.root
    state_root = os.path.join(root, "state")
    state = ExperimentStateStore(state_root if os.path.isdir(state_root) else None)
    if state.root is None or not state.has_state(args.experiment):
        print(f"no persisted state for experiment {args.experiment!r} under "
              f"{state_root}", file=sys.stderr)
        return 1
    exp = state.load(args.experiment)
    lease = recovery.read_lease(state_root)
    jdir = recovery.journal_dir(root)
    records = (
        recovery.RecoveryJournal(jdir).records(args.experiment)
        if os.path.isdir(jdir)
        else []
    )
    store = open_store(os.path.join(root, "observations.db"))
    try:
        inflight = []
        for t in state.list_trials(args.experiment):
            if t.is_terminal and not any(
                c.type == "Killed" and c.reason == "SchedulerShutdown"
                for c in t.conditions
            ):
                continue
            workdir = os.path.join(root, "trials", args.experiment, t.name)
            ck_time = recovery.latest_checkpoint_time(workdir)
            rows = store.get_observation_log(t.name)
            preserved = (
                sum(1 for r in rows if r.timestamp <= ck_time)
                if ck_time is not None
                else 0
            )
            inflight.append(
                {
                    "trial": t.name,
                    "condition": t.condition.value,
                    "reason": t.current_reason,
                    "checkpointed": ck_time is not None,
                    "rows": len(rows),
                    "rowsPreservedOnRecovery": preserved,
                }
            )
    finally:
        store.close()
    tail = records[-args.journal_tail:] if args.journal_tail else records
    if args.format == "json":
        print(json.dumps(
            {
                "experiment": args.experiment,
                "status": exp.status.condition.value,
                "lease": lease.to_dict(),
                "journal": {"records": len(records), "tail": tail},
                "inflight": inflight,
            },
            indent=2, sort_keys=True,
        ))
        return 0
    print(f"experiment: {args.experiment} ({exp.status.condition.value})")
    holder = lease.payload.get("owner") or "-"
    if not lease.exists:
        print("lease:      none (no controller has locked this root)")
    else:
        verdict = (
            "released" if lease.state == "released"
            else "EXPIRED" if lease.expired
            else "held" if lease.holder_alive
            else "holder dead (takeable)"
        )
        print(
            f"lease:      {verdict} by {holder} (pid "
            f"{lease.payload.get('pid')}, fence {lease.payload.get('fence')}, "
            f"age {lease.age_seconds:.1f}s / ttl {lease.payload.get('ttl')}s)"
        )
    print(f"journal:    {len(records)} record(s) under {jdir}")
    for rec in tail:
        extra = rec.get("trial") or ",".join(rec.get("trials", []) or [])
        print(f"  seq {rec.get('seq'):>6}  {rec.get('op'):<9} {extra}")
    if not inflight:
        print("in-flight:  none (a recovery load would requeue nothing)")
    else:
        print(f"in-flight:  {len(inflight)} trial(s) a recovery load would requeue:")
        _table(
            ["TRIAL", "CONDITION", "REASON", "CKPT", "ROWS", "PRESERVED"],
            [
                (i["trial"], i["condition"], i["reason"],
                 "yes" if i["checkpointed"] else "no",
                 i["rows"], i["rowsPreservedOnRecovery"])
                for i in inflight
            ],
        )
    return 0


def cmd_replicas(args) -> int:
    """Offline placement table of the sharded control plane (ISSUE 15):
    replica registrations + per-experiment placement leases, read straight
    from ``<root>/placement/`` — no controller constructed, so it never
    contends a live lease (the `recover`/`devices` CLI shape)."""
    from .controller.placement import placement_table

    table = placement_table(args.root)
    if args.format == "json":
        print(json.dumps(table, indent=2, sort_keys=True))
        return 0
    replicas, leases = table["replicas"], table["leases"]
    if not replicas and not leases:
        print(f"no placement state under {args.root}/placement "
              "(sharded mode never ran here)")
        return 0
    print(f"replicas ({len(replicas)}):")
    _table(
        ["REPLICA", "ALIVE", "PID", "CLAIMED", "CAPACITY", "AGE", "URL"],
        [
            (
                r.get("replica", "-"),
                "yes" if r.get("alive") else "no",
                r.get("pid", "-"),
                len(r.get("claimed", [])),
                r.get("capacity", "-"),
                f"{r['ageSeconds']:.1f}s" if r.get("ageSeconds") is not None else "-",
                r.get("url", "-"),
            )
            for r in replicas
        ],
    )
    print(f"\nplacement leases ({len(leases)}):")
    _table(
        ["EXPERIMENT", "REPLICA", "STATE", "FENCE", "AGE", "HOLDER"],
        [
            (
                l.get("experiment", "-"),
                l.get("replica") or "-",
                ("EXPIRED" if l.get("expired") and l.get("state") == "active"
                 else l.get("state", "-")),
                l.get("fence", "-"),
                f"{l['ageSeconds']:.1f}s" if l.get("ageSeconds") is not None else "-",
                ("alive" if l.get("holderAlive") else "dead"),
            )
            for l in leases
        ],
    )
    return 0


def cmd_tenants(args) -> int:
    """Offline tenant registry table (ISSUE 17): scoped tokens, quotas and
    currently-claimed experiments, read straight from ``<root>/tenants/``
    and ``<root>/placement/`` — no controller constructed (the `replicas`
    CLI shape), so it works against a live multi-replica deployment."""
    from .service.tenancy import TenantRegistry, claimed_experiments

    reg = TenantRegistry(args.root)
    records = reg.records()
    if args.format == "json":
        doc = []
        for rec in records:
            d = rec.to_doc()
            if not args.show_tokens:
                d["tokens"] = {s: "***" for s in d.get("tokens", {})}
            d["claimedExperiments"] = claimed_experiments(args.root, rec.name)
            doc.append(d)
        print(json.dumps(doc, indent=2, sort_keys=True))
        return 0
    if not records:
        print(f"no tenants registered under {args.root}/tenants "
              "(create one with the TenantRegistry API)")
        return 0
    print(f"tenants ({len(records)}):")
    _table(
        ["TENANT", "SCOPES", "ADMIT/MIN", "MAX-EXP", "DEVICES", "WEIGHT",
         "CLAIMED", "HISTORY"],
        [
            (
                rec.name,
                ",".join(sorted(rec.tokens)),
                f"{rec.admission_per_minute:g}" if rec.admission_per_minute else "-",
                rec.max_experiments or "-",
                rec.device_quota if rec.device_quota is not None else "-",
                f"{rec.fair_share_weight:g}",
                len(claimed_experiments(args.root, rec.name)),
                "shared" if rec.shared_history else "scoped",
            )
            for rec in records
        ],
    )
    return 0


def cmd_metrics(args) -> int:
    import os

    from .db.store import open_store

    db = os.path.join(args.root, "observations.db") if args.root else None
    store = open_store(db)
    for log in store.get_observation_log(args.trial, metric_name=args.metric):
        print(f"{log.timestamp:.3f}\t{log.metric_name}\t{log.value}")
    store.close()
    return 0


def cmd_algorithms(args) -> int:
    from .earlystop.medianstop import registered_early_stoppers
    from .suggest.base import registered_algorithms

    print("suggestion:", ", ".join(sorted(registered_algorithms())))
    print("early-stopping:", ", ".join(sorted(registered_early_stoppers())))
    return 0


def cmd_check(args) -> int:
    """Static analysis over the tree (ISSUE 6 tentpole): recompile/host-sync
    hazards, lock discipline, repo invariants. A thin shim — the engine owns
    its own argparse so `python -m katib_tpu.analysis.engine` behaves
    identically in CI."""
    from .analysis.engine import main as check_main

    forwarded = list(args.paths)
    forwarded += ["--format", args.format]
    if args.baseline:
        forwarded.append("--baseline")
    if args.no_suppressions:
        forwarded.append("--no-suppressions")
    return check_main(forwarded)


def cmd_analyze(args) -> int:
    """Semantic program analysis (ISSUE 7 tentpole): trace the trial's
    abstract program under the experiment's search space (eval_shape /
    make_jaxpr only — no compilation, no execution, no devices) and print
    the compile fingerprint, the per-parameter classification, and the
    jaxpr cost model. Accepts an experiment spec file (JSON/YAML, plain or
    CRD envelope) or a bare module:fn target."""
    import os

    from .analysis.program import analyze_entry, analyze_spec, filter_findings

    target = args.target
    if os.path.exists(target):
        from .api.spec import load_experiment_document

        try:
            with open(target) as f:
                spec = load_experiment_document(f.read())
            analysis = analyze_spec(spec)
        except (ValueError, KeyError, TypeError) as e:
            print(f"invalid experiment spec: {e}", file=sys.stderr)
            return 2
    else:
        try:
            analysis = analyze_entry(target)
        except ValueError as e:
            print(str(e), file=sys.stderr)
            return 2

    findings, n_suppressed = filter_findings(list(analysis.findings))
    if args.format == "json":
        doc = analysis.to_dict()
        doc["findings"] = [f.to_dict() for f in findings]
        doc["suppressed"] = n_suppressed
        print(json.dumps(doc, indent=2, sort_keys=True))
        return 1 if findings else 0

    print(f"target:      {analysis.target}")
    if analysis.digest:
        print(f"digest:      {analysis.digest}")
    if not analysis.analyzable:
        print("analyzable:  no"
              + (f" ({analysis.error})" if analysis.error else ""))
    else:
        print(f"fingerprint: {analysis.fingerprint}")
    if analysis.params:
        print("\nparameters:")
        _table(
            ["NAME", "TYPE", "CLASS", "CORNERS", "DISTINCT-PROGRAMS"],
            [
                (p.name, p.type, p.cls, ", ".join(p.corner_values),
                 str(p.distinct_fingerprints))
                for p in analysis.params
            ],
        )
    if analysis.cost is not None:
        c = analysis.cost
        print("\ncost (baseline program, static estimate):")
        _table(
            ["FLOPS", "PARAM-BYTES", "INPUT-BYTES", "OUTPUT-BYTES",
             "PEAK-HBM(LOWER-BOUND)", "EQNS"],
            [(f"{c.flops:.4g}", str(c.param_bytes), str(c.input_bytes),
              str(c.output_bytes), str(c.peak_bytes), str(c.eqns))],
        )
        for note in c.notes:
            print(f"  note: {note}")
    if findings:
        print(f"\nfindings ({n_suppressed} suppressed):")
        for f in findings:
            print(f"  {f.path}:{f.line}: {f.rule} {f.message}")
    else:
        print(f"\nno findings ({n_suppressed} suppressed)")
    return 1 if findings else 0


def cmd_ui(args) -> int:
    from .ui.server import serve_ui

    ctrl = _controller(args.root)
    _load_all(ctrl, args.root)
    print(f"serving dashboard on http://{args.host}:{args.port}")
    serve_ui(ctrl, host=args.host, port=args.port, block=True)
    return 0


def cmd_serve(args) -> int:
    """Run the algorithm/DB gRPC service standalone — the reference's
    suggestion-pod / db-manager deployment shape (cmd/suggestion/*/main.py,
    cmd/db-manager). Controllers on other hosts reach it via
    service.rpc.ApiClient / RemoteSuggester / RemoteObservationStore."""
    import os

    from .db.store import open_store
    from .service.rpc import serve

    db_path = os.path.join(args.root, "observations.db") if args.root else None
    store = open_store(db_path)
    server = serve(port=args.port, store=store)
    print(f"serving suggestion/early-stopping/db-manager gRPC on :{server.bound_port}")
    server.wait_for_termination()
    return 0


def _load_all(ctrl, root: Optional[str]) -> None:
    """Hydrate persisted experiments from the state root."""
    for name in ctrl.state.persisted_experiments():
        ctrl.state.load(name)


def _print_status(exp) -> None:
    s = exp.status
    print(f"name:      {exp.name}")
    print(f"status:    {s.condition.value} ({s.reason.value or 'n/a'})")
    print(
        "trials:    "
        f"{s.trials} total | {s.trials_succeeded} succeeded | {s.trials_running} running | "
        f"{s.trials_failed} failed | {s.trials_early_stopped} early-stopped | "
        f"{s.trials_killed} killed | {s.trials_metrics_unavailable} metrics-unavailable"
    )
    opt = s.current_optimal_trial
    if opt.best_trial_name:
        print(f"best:      {opt.best_trial_name}")
        print(f"  params:  {json.dumps({a.name: a.value for a in opt.parameter_assignments})}")
        for m in opt.observation.metrics:
            print(f"  {m.name}: min={m.min} max={m.max} latest={m.latest}")


def _table(headers, rows) -> None:
    widths = [len(h) for h in headers]
    for row in rows:
        for i, cell in enumerate(row):
            widths[i] = max(widths[i], len(str(cell)))
    fmt = "  ".join(f"{{:<{w}}}" for w in widths)
    print(fmt.format(*headers))
    for row in rows:
        print(fmt.format(*[str(c) for c in row]))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="katib-tpu", description=__doc__.split("\n")[0])
    p.add_argument("--root", default=".katib-tpu", help="state root directory")
    sub = p.add_subparsers(dest="command", required=True)

    run_p = sub.add_parser(
        "run",
        help="create + drive an experiment from a JSON or YAML spec "
        "(plain spec or the Katib CRD envelope)",
    )
    run_p.add_argument("spec")
    run_p.add_argument("--timeout", type=float, default=None)
    run_p.add_argument("--devices", type=int, default=None, help="pool this many abstract device slots (default: the host's real accelerator devices for in-process trials on a TPU host, else 8 abstract slots)")
    run_p.set_defaults(fn=cmd_run)

    res_p = sub.add_parser(
        "resume", help="resume a persisted experiment after a controller restart"
    )
    res_p.add_argument("name")
    res_p.add_argument("--timeout", type=float, default=None)
    res_p.add_argument("--devices", type=int, default=None)
    res_p.set_defaults(fn=cmd_resume)

    sub.add_parser("list", help="list experiments").set_defaults(fn=cmd_list)

    st = sub.add_parser("status", help="experiment status")
    st.add_argument("name")
    st.set_defaults(fn=cmd_status)

    tr = sub.add_parser("trials", help="trial table for an experiment")
    tr.add_argument("name")
    tr.set_defaults(fn=cmd_trials)

    qu = sub.add_parser(
        "queue",
        help="fair-share scheduler queue (pending trials with priority/wait)",
    )
    qu.add_argument(
        "--url",
        default=None,
        help="base URL of a running 'katib-tpu ui' server for the live "
        "/api/queue view (incl. fair-share deficits)",
    )
    qu.set_defaults(fn=cmd_queue)

    im = sub.add_parser("importance", help="parameter-importance table for an experiment")
    im.add_argument("name")
    im.set_defaults(fn=cmd_importance)

    tc = sub.add_parser(
        "trace",
        help="trial lifecycle span tree (durations + %% of trial wall-clock)",
    )
    tc.add_argument("experiment")
    tc.add_argument(
        "trial", nargs="?", default=None,
        help="omit for the experiment-level view: every trial's trace, "
        "worst-first by root-span duration (offline from --root)",
    )
    tc.add_argument(
        "--url",
        default=None,
        help="base URL of a running 'katib-tpu ui' server for the live "
        "trace (else reads the persisted trace under <root>/traces/)",
    )
    tc.add_argument(
        "--format", choices=("tree", "perfetto"), default="tree",
        help="'perfetto' dumps a Chrome trace_event file (ui.perfetto.dev) "
        "instead of rendering the tree",
    )
    tc.add_argument(
        "--output", default=None,
        help="perfetto dump path (default <experiment>[_<trial>]"
        ".perfetto.json in the working directory)",
    )
    tc.set_defaults(fn=cmd_trace)

    fl = sub.add_parser(
        "fleet",
        help="fleet status: every registered replica's liveness, claims, "
        "rpc/ingest counters and per-tenant SLO standing in one table",
    )
    fl.add_argument(
        "--token", default=None,
        help="bearer token for the replicas' status endpoints (tenancy "
        "deployments need an admin-scoped token)",
    )
    fl.add_argument(
        "--watch", action="store_true",
        help="refresh the table every --interval seconds until interrupted",
    )
    fl.add_argument("--interval", type=float, default=5.0)
    fl.set_defaults(fn=cmd_fleet)

    pf = sub.add_parser(
        "perf",
        help="per-trial step timing, throughput, MFU and retraces from the "
        "persisted katib-tpu/perf/ rows (needs runtime.step_stats on)",
    )
    pf.add_argument("experiment")
    pf.add_argument(
        "--format", choices=("table", "json"), default="table",
        help="table (default) or the full per-trial summaries as JSON",
    )
    pf.set_defaults(fn=cmd_perf)

    tp = sub.add_parser(
        "top",
        help="per-trial resource table (RSS / CPU / HBM / last-report age)",
    )
    tp.add_argument(
        "--url",
        default=None,
        help="base URL of a running 'katib-tpu ui' server for the live "
        "/api/telemetry view (else reads persisted series under "
        "<root>/telemetry/)",
    )
    tp.add_argument(
        "--watch", action="store_true",
        help="refresh the table every --interval seconds until interrupted",
    )
    tp.add_argument("--interval", type=float, default=5.0)
    tp.set_defaults(fn=cmd_top)

    cp = sub.add_parser(
        "compile",
        help="AOT compile service registry (fingerprint, state, cost, "
        "compile time, trials served)",
    )
    cp.add_argument(
        "--url",
        default=None,
        help="base URL of a running 'katib-tpu ui' server for the live "
        "/api/compile view (else reads the snapshot under "
        "<root>/compilesvc/)",
    )
    cp.set_defaults(fn=cmd_compile)

    rg = sub.add_parser(
        "rungs",
        help="multi-fidelity ladder: per-bracket, per-rung population, "
        "paused/promoted/pruned counts and best objective (offline from "
        "the state root)",
    )
    rg.add_argument("experiment")
    rg.add_argument(
        "--format",
        choices=("table", "json"),
        default="table",
        help="table (default) or the full report as JSON for scripting",
    )
    rg.set_defaults(fn=cmd_rungs)

    me = sub.add_parser("metrics", help="raw observation log for a trial")
    me.add_argument("trial")
    me.add_argument("--metric", default=None)
    me.set_defaults(fn=cmd_metrics)

    dv = sub.add_parser(
        "devices",
        help="device plane lease/health state (offline, from the "
             "<root>/deviceplane snapshot)",
    )
    dv.set_defaults(fn=cmd_devices)

    po = sub.add_parser(
        "population",
        help="fused population sweep: per-generation best/median + "
        "in-flight checkpoint state",
    )
    po.add_argument("experiment")
    po.set_defaults(fn=cmd_population)

    sub.add_parser("algorithms", help="list registered algorithms").set_defaults(fn=cmd_algorithms)

    ck = sub.add_parser(
        "check",
        help="static analysis: recompile hazards, lock discipline, repo "
        "invariants (exit 1 on findings)",
    )
    ck.add_argument("paths", nargs="*", help="files/dirs (default: katib_tpu/)")
    ck.add_argument("--format", choices=("text", "json", "sarif"), default="text")
    ck.add_argument(
        "--baseline", action="store_true",
        help="record current findings to analysis/baseline.json and exit 0",
    )
    ck.add_argument("--no-suppressions", action="store_true")
    ck.set_defaults(fn=cmd_check)

    an = sub.add_parser(
        "analyze",
        help="semantic program analysis: compile fingerprint, parameter "
        "classification, cost table (exit 1 on KTX findings)",
    )
    an.add_argument(
        "target",
        help="experiment spec file (JSON/YAML) or module:fn entry point",
    )
    an.add_argument("--format", choices=("text", "json"), default="text")
    an.set_defaults(fn=cmd_analyze)

    ui = sub.add_parser("ui", help="serve the web dashboard + REST API")
    ui.add_argument("--host", default="127.0.0.1")
    ui.add_argument("--port", type=int, default=8080)
    ui.set_defaults(fn=cmd_ui)

    rc = sub.add_parser(
        "recover",
        help="offline crash-recovery inspection: lease state, journal tail, "
        "and the in-flight trials a recovery load would requeue",
    )
    rc.add_argument("experiment")
    rc.add_argument("--journal-tail", type=int, default=20,
                    help="journal records to show (0 = all)")
    rc.add_argument("--format", choices=("text", "json"), default="text")
    rc.set_defaults(fn=cmd_recover)

    sv = sub.add_parser(
        "serve", help="run the suggestion/early-stopping/db-manager gRPC service"
    )
    sv.add_argument("--port", type=int, default=6789)
    sv.set_defaults(fn=cmd_serve)

    rp = sub.add_parser(
        "replicas",
        help="sharded-control-plane placement table (replica registrations "
        "+ per-experiment placement leases), offline from <root>/placement/",
    )
    rp.add_argument("--format", choices=("text", "json"), default="text")
    rp.set_defaults(fn=cmd_replicas)

    tp = sub.add_parser(
        "tenants",
        help="multi-tenant registry table (scopes, quotas, claimed "
        "experiments), offline from <root>/tenants/",
    )
    tp.add_argument("--format", choices=("text", "json"), default="text")
    tp.add_argument("--show-tokens", action="store_true",
                    help="print raw token values in --format json "
                    "(default: redacted)")
    tp.set_defaults(fn=cmd_tenants)

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
