"""CPU rehearsals of the control plane: 18 standalone scenarios.

Each scenario drives one plane of the package (observation store, tracing,
step stats, telemetry, the analyzers, the compile service, fused
populations, the suggesters, multi-fidelity, recovery, the replica control
plane, tenancy, framed ingest) through its public path on the CPU, most of
them once with the plane on and once with its legacy twin, and checks that
both sides agree. What a scenario prints are counts and identities (rows,
trials, device-epochs, "bit_identical"); a rate or a ratio among them was
timed on this sandbox's shared CPU and is never a speed (ROADMAP aim 1).
What the model programs cost on the chip is measured by ``benchmarks/``
alone (``BENCHMARK.json``, ``PERF.md``).

    python bench.py <scenario> [--smoke] [--distributed]

prints ONE JSON line ``{"metric": <scenario>, ...}``. ``--smoke`` trims the
sizes to the tier-1 wiring run (``tests/test_bench_budget.py``,
``scripts/check.sh``); ``--distributed`` is ``tracing_overhead``'s. With no
or an unknown name the exit code is 2 and the names are listed.
"""

import json
import os
import subprocess
import sys
import time


def _bench_obslog_report_throughput(smoke: bool = False):
    """Observation data plane (db/store.py): rows/sec of single-row
    ``ctx.report``-shaped appends, per-report commit (plain SQLite store)
    vs the BufferedObservationStore group-commit pipeline. The buffered
    number includes a final flush() barrier so both sides end durable;
    read-your-writes is spot-checked mid-stream. ``smoke`` trims the row
    count for the tier-1 wiring test (tests/test_bench_budget.py) — it
    exercises the same end-to-end path without the timed-run budget."""
    import shutil
    import tempfile

    from katib_tpu.db.store import (
        BufferedObservationStore, MetricLog, SqliteObservationStore,
    )

    n_reports = 300 if smoke else int(os.environ.get("BENCH_OBSLOG_ROWS", "4000"))
    root = tempfile.mkdtemp(prefix="bench-obslog-")
    try:
        sync = SqliteObservationStore(os.path.join(root, "sync.db"))
        t0 = time.perf_counter()
        for i in range(n_reports):
            sync.report_observation_log(
                "trial-sync", [MetricLog(float(i), "loss", str(float(i)))]
            )
        sync_s = time.perf_counter() - t0
        sync.close()

        buf = BufferedObservationStore(
            SqliteObservationStore(os.path.join(root, "buffered.db"))
        )
        t0 = time.perf_counter()
        for i in range(n_reports):
            buf.report_observation_log(
                "trial-buf", [MetricLog(float(i), "loss", str(float(i)))]
            )
            if i == n_reports // 2:
                # read-your-writes: an unflushed append is already readable
                assert buf.get_observation_log("trial-buf")[-1].timestamp == float(i)
        buf.flush()
        buffered_s = time.perf_counter() - t0
        durable = len(buf.inner.get_observation_log("trial-buf"))
        stats = buf.stats()
        buf.close()
        return {
            "n_reports": n_reports,
            "workload": "1-row report per call, WAL sqlite, tmpdir",
            "sync_s": round(sync_s, 4),
            "buffered_s": round(buffered_s, 4),
            "sync_rows_per_s": round(n_reports / max(sync_s, 1e-9), 1),
            "buffered_rows_per_s": round(n_reports / max(buffered_s, 1e-9), 1),
            "speedup": round(sync_s / max(buffered_s, 1e-9), 2),
            "durable_rows": durable,
            "rows_complete": durable == n_reports,
            "group_commits": stats["flush_total"],
            "max_batch_rows": stats["flush_batch_rows_max"],
            "smoke": smoke,
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _bench_obslog_fold_latency(smoke: bool = False):
    """Poll-path cost vs log size: folding a trial's observation log via the
    incremental fold index (store.folded, O(metrics)) vs the
    fold_observation rescan over get_observation_log (O(rows × metrics) —
    what the scheduler's completion/poll sites paid before). Every size
    asserts the two answers are identical (the property the index must
    hold); the logs include non-numeric values and timestamp ties."""
    import shutil
    import tempfile

    from katib_tpu.db.store import (
        BufferedObservationStore, MetricLog, SqliteObservationStore,
        fold_observation,
    )

    sizes = [200, 1000] if smoke else [1000, 10000, 50000]
    names = ["accuracy", "loss", "note"]
    root = tempfile.mkdtemp(prefix="bench-obslog-fold-")
    out = []
    try:
        for n_rows in sizes:
            store = BufferedObservationStore(
                SqliteObservationStore(os.path.join(root, f"fold-{n_rows}.db"))
            )
            batch = []
            for i in range(n_rows):
                name = names[i % len(names)]
                value = "warming-up" if name == "note" else str(0.1 + (i % 97) / 100.0)
                # integer-div timestamps create ties within each quartet
                batch.append(MetricLog(float(i // 4), name, value))
                if len(batch) >= 256:
                    store.report_observation_log("t", batch)
                    batch = []
            if batch:
                store.report_observation_log("t", batch)
            store.flush()
            reps = 5 if smoke else 20
            t0 = time.perf_counter()
            for _ in range(reps):
                indexed = store.folded("t", names)
            indexed_us = (time.perf_counter() - t0) / reps * 1e6
            t0 = time.perf_counter()
            for _ in range(reps):
                rescan = fold_observation(store.get_observation_log("t"), names)
            rescan_us = (time.perf_counter() - t0) / reps * 1e6
            store.close()
            out.append({
                "rows": n_rows,
                "indexed_us": round(indexed_us, 1),
                "rescan_us": round(rescan_us, 1),
                "speedup": round(rescan_us / max(indexed_us, 1e-9), 1),
                "identical": indexed == rescan,
            })
        return {"metrics_per_trial": len(names), "sizes": out, "smoke": smoke}
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _bench_tracing_overhead(smoke: bool = False, distributed: bool = False):
    """Trial lifecycle tracing (katib_tpu/tracing.py): end-to-end trials/sec
    of an in-process experiment with ``runtime.tracing`` on vs off. The
    target is <3% overhead when on and ~0% when off (off IS the
    KATIB_TPU_TRACING=0 path: every instrumentation site reduces to one
    boolean check). Runs interleaved on/off passes and keeps each side's
    best to shed scheduler noise on shared CI boxes. ``smoke`` trims the
    trial count for the tier-1 wiring test (tests/test_bench_budget.py).
    ``distributed`` (``--distributed``) switches to the 3-replica wire
    measurement instead (ISSUE 19)."""
    if distributed:
        return _bench_tracing_overhead_distributed(smoke)
    from katib_tpu.api.spec import (
        AlgorithmSpec, ExperimentSpec, FeasibleSpace, ObjectiveSpec,
        ObjectiveType, ParameterSpec, ParameterType, TrialTemplate,
    )
    from katib_tpu.config import KatibConfig
    from katib_tpu.controller.experiment import ExperimentController

    n_trials = 12 if smoke else int(os.environ.get("BENCH_TRACING_TRIALS", "64"))
    reports = 20 if smoke else 100     # report() is the hottest traced site
    work = 200 if smoke else 20000     # busy-work per step: an empty trial
    # loop would measure thread-scheduling noise (±15% run-to-run on shared
    # CI), not tracing — real trials compute between reports, and the <3%
    # target is tracing cost relative to a realistically-busy trial

    def trial_fn(assignments, ctx):
        x = float(assignments.get("x", "0.5"))
        for i in range(reports):
            acc = 0
            for j in range(work):
                acc += j & 7
            x = x * 0.999 + 1e-9 * acc
            ctx.report(score=x)

    counter = {"n": 0}

    def run_once(tracing_on: bool) -> float:
        counter["n"] += 1
        cfg = KatibConfig()
        cfg.runtime.tracing = tracing_on
        cfg.runtime.obslog_buffered = False  # memory store either way
        ctrl = ExperimentController(
            root_dir=None, devices=list(range(8)), persist=False, config=cfg
        )
        name = f"tracing-bench-{counter['n']}"
        spec = ExperimentSpec(
            name=name,
            parameters=[
                ParameterSpec(
                    "x", ParameterType.DOUBLE, FeasibleSpace(min="0.1", max="1.0")
                )
            ],
            objective=ObjectiveSpec(
                type=ObjectiveType.MAXIMIZE, objective_metric_name="score"
            ),
            algorithm=AlgorithmSpec("random"),
            trial_template=TrialTemplate(function=trial_fn),
            max_trial_count=n_trials,
            parallel_trial_count=8,
        )
        try:
            ctrl.create_experiment(spec)
            t0 = time.perf_counter()
            exp = ctrl.run(name, timeout=300)
            dt = time.perf_counter() - t0
            assert exp.status.trials_succeeded == n_trials, (
                f"{exp.status.trials_succeeded}/{n_trials} succeeded"
            )
            if tracing_on:
                trial = ctrl.state.list_trials(name)[0]
                trace = ctrl.tracer.trial_trace(name, trial.name)
                assert trace and trace["spans"], "tracing on but no spans recorded"
            else:
                assert not ctrl.tracer.enabled
            return dt
        finally:
            ctrl.close()

    run_once(False)  # warmup: thread/JIT-free path, but import + state costs
    passes = 2 if smoke else 3
    on_s, off_s = [], []
    for _ in range(passes):
        off_s.append(run_once(False))
        on_s.append(run_once(True))
    on, off = min(on_s), min(off_s)
    overhead_pct = (on - off) / off * 100.0
    return {
        "trials": n_trials,
        "reports_per_trial": reports,
        "passes": passes,
        "off_s": round(off, 4),
        "on_s": round(on, 4),
        "off_trials_per_s": round(n_trials / off, 1),
        "on_trials_per_s": round(n_trials / on, 1),
        "overhead_pct": round(overhead_pct, 2),
        "target_pct": 3.0,
        "within_target": overhead_pct < 3.0,
        "smoke": smoke,
    }


def _bench_tracing_overhead_distributed(smoke: bool = False):
    """Distributed tracing cost (ISSUE 19): the same cheap-experiment batch
    driven through THREE real replica subprocesses over the wire, with the
    whole distributed plane armed (KATIB_TPU_WIRE_TRACING=1 +
    KATIB_TPU_TRACING=1: traceparent headers on every RPC, server-side rpc
    spans, per-tenant SLO histograms, the durable wire span sink) vs both
    knobs off. Target: <3% aggregate trials/sec cost. Uses the
    control_plane_scaling harness shape — replica subprocesses, the
    client-side placement router, subprocess trials reporting over the
    wire — so the measured path IS the production wire path."""
    import shutil
    import tempfile

    from katib_tpu.client.katib_client import ReplicaRouter

    replicas = 3
    n_exps = int(os.environ.get("BENCH_TRO_EXPERIMENTS", "3" if smoke else "9"))
    n_trials = 2 if smoke else 4
    epochs = 3 if smoke else 6
    dwell = 0.02 if smoke else 0.05
    parallel = 2 if smoke else 4
    repo = os.path.dirname(os.path.abspath(__file__))

    def spec_for(name):
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
                "entryPoint": "cp_trial:run_trial",
                "trialParameters": [{"name": "x", "reference": "x"}],
            },
            "maxTrialCount": n_trials,
            "parallelTrialCount": parallel,
            "resumePolicy": "FromVolume",
        }

    def is_done(status_doc):
        if not status_doc:
            return False
        return any(
            c.get("type") in ("Succeeded", "Failed") and c.get("status")
            for c in status_doc.get("status", {}).get("conditions", [])
        )

    def run_once(wire_on: bool) -> float:
        root = tempfile.mkdtemp(prefix="bench-trace-dist-")
        with open(os.path.join(root, "cp_trial.py"), "w") as f:
            f.write(_CP_TRIAL_MODULE.format(epochs=epochs, dwell=dwell))
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": (
                repo + os.pathsep + root + os.pathsep + env.get("PYTHONPATH", "")
            ).rstrip(os.pathsep),
            "KATIB_TPU_REPLICAS": str(replicas),
            "KATIB_TPU_REPLICA_CAPACITY": str(n_exps + 4),
            "KATIB_TPU_PLACEMENT_LEASE_SECONDS": "8",
            "KATIB_TPU_TELEMETRY": "0",
            "KATIB_TPU_COMPILE_SERVICE": "0",
            "KATIB_TPU_OBSLOG_BUFFERED": "0",
            "KATIB_TPU_TRACING": "1" if wire_on else "0",
            "KATIB_TPU_WIRE_TRACING": "1" if wire_on else "0",
        })
        env.pop("KATIB_TPU_CHAOS", None)
        procs, logs = [], []
        deadline = time.time() + 420.0
        try:
            for i in range(replicas):
                out = open(os.path.join(root, f"r{i}.log"), "w+")
                logs.append(out)
                procs.append(subprocess.Popen(
                    [sys.executable, "-m", "katib_tpu.controller.replica",
                     "--root", root, "--replica-id", f"r{i}", "--devices", "4"],
                    env=env, stdout=out, stderr=out, text=True,
                ))
            router = ReplicaRouter(root)
            while len(router.live_replicas()) < replicas:
                if time.time() > deadline:
                    raise TimeoutError(
                        f"replicas never registered; see {root}/r*.log"
                    )
                time.sleep(0.2)
            warm = []
            for i in range(replicas):
                w = dict(spec_for(f"trace-warm-{i}"))
                w["maxTrialCount"] = 1
                w["parallelTrialCount"] = 1
                router.create_experiment(w)
                warm.append(f"trace-warm-{i}")
            while not all(is_done(router.experiment_status(w)) for w in warm):
                if time.time() > deadline:
                    raise TimeoutError("warmup experiments never completed")
                time.sleep(0.2)
            names = [f"trace-{i:02d}" for i in range(n_exps)]
            t0 = time.time()
            for name in names:
                router.create_experiment(spec_for(name))
            pending = set(names)
            while pending:
                if time.time() > deadline:
                    raise TimeoutError(
                        f"{len(pending)} experiment(s) never completed; "
                        f"see {root}/r*.log"
                    )
                for name in list(pending):
                    if is_done(router.experiment_status(name)):
                        pending.discard(name)
                time.sleep(0.15)
            wall = time.time() - t0
            if wire_on:
                # the on side must actually have traced across the wire —
                # a silently-dark plane would "win" the comparison
                wdir = os.path.join(root, "traces", "wire")
                assert os.path.isdir(wdir) and os.listdir(wdir), (
                    "wire tracing on but no wire spans persisted under "
                    f"{wdir}"
                )
            return wall
        finally:
            for proc in procs:
                if proc.poll() is None:
                    proc.terminate()
            for proc in procs:
                if proc.poll() is None:
                    try:
                        proc.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        proc.kill()
            for out in logs:
                out.close()
            shutil.rmtree(root, ignore_errors=True)

    passes = 1 if smoke else 2
    on_s, off_s = [], []
    for _ in range(passes):
        off_s.append(run_once(False))
        on_s.append(run_once(True))
    on, off = min(on_s), min(off_s)
    total = n_exps * n_trials
    overhead_pct = (on - off) / off * 100.0
    return {
        "distributed": True,
        "replicas": replicas,
        "experiments": n_exps,
        "trials": total,
        "epochs": epochs,
        "passes": passes,
        "off_s": round(off, 3),
        "on_s": round(on, 3),
        "off_trials_per_s": round(total / off, 2),
        "on_trials_per_s": round(total / on, 2),
        "overhead_pct": round(overhead_pct, 2),
        "target_pct": 3.0,
        "within_target": overhead_pct < 3.0,
        "smoke": smoke,
    }


def _bench_step_stats_overhead(smoke: bool = False):
    """Step-statistics plane cost (ISSUE 20): end-to-end packs/sec of a
    pack_size=8 in-process sweep with ``runtime.step_stats`` on vs off.
    Target <3% overhead when on (off IS the KATIB_TPU_STEP_STATS=0 path:
    every consult is one ``is None`` check). Same interleaved-passes,
    keep-each-side's-best shape as tracing_overhead. Also asserts the
    knob-off run writes zero katib-tpu/perf/ rows and exports none of the
    step metric families, and runs one injected-straggler pass
    (KATIB_TPU_STEP_STATS_INJECT=straggle=...) that must fire exactly one
    GangStraggler warning event."""
    from katib_tpu.api.spec import (
        AlgorithmSpec, ExperimentSpec, FeasibleSpace, ObjectiveSpec,
        ObjectiveType, ParameterSpec, ParameterType, TrialResources,
        TrialTemplate,
    )
    from katib_tpu.config import KatibConfig
    from katib_tpu.controller.experiment import ExperimentController
    from katib_tpu.runtime.packed import population_of, report_population
    from katib_tpu.runtime.stepstats import PERF_PREFIX

    pack_size = 8
    reports = 20 if smoke else 100     # report_population is the hot site
    work = 200 if smoke else 20000     # busy-work per step (see
    # tracing_overhead: an empty loop measures scheduler noise, not the
    # plane; the <3% target is cost relative to a realistically-busy pack)
    lrs = [str(round(0.1 + 0.1 * i, 1)) for i in range(pack_size)]

    def pack_fn(assignments, ctx=None):
        pop = population_of(assignments)
        lr = pop["lr"]
        for step in range(reports):
            acc = 0
            for j in range(work):
                acc += j & 7
            report_population(ctx, score=lr * (step + 1) + 1e-9 * acc,
                              examples=pack_size)

    pack_fn.supports_packing = True
    counter = {"n": 0}

    def run_once(stats_on: bool, inject: str = ""):
        counter["n"] += 1
        prev = os.environ.pop("KATIB_TPU_STEP_STATS_INJECT", None)
        if inject:
            os.environ["KATIB_TPU_STEP_STATS_INJECT"] = inject
        cfg = KatibConfig()
        cfg.runtime.step_stats = stats_on
        cfg.runtime.obslog_buffered = False
        ctrl = ExperimentController(
            root_dir=None, devices=list(range(8)), persist=False, config=cfg
        )
        name = f"stepstats-bench-{counter['n']}"
        spec = ExperimentSpec(
            name=name,
            parameters=[
                ParameterSpec("lr", ParameterType.DISCRETE, FeasibleSpace(list=lrs))
            ],
            objective=ObjectiveSpec(
                type=ObjectiveType.MAXIMIZE, objective_metric_name="score"
            ),
            algorithm=AlgorithmSpec("grid"),
            trial_template=TrialTemplate(
                function=pack_fn, resources=TrialResources(pack_size=pack_size)
            ),
            max_trial_count=pack_size,
            parallel_trial_count=pack_size,
        )
        try:
            ctrl.create_experiment(spec)
            t0 = time.perf_counter()
            exp = ctrl.run(name, timeout=300)
            dt = time.perf_counter() - t0
            assert exp.status.trials_succeeded == pack_size, (
                f"{exp.status.trials_succeeded}/{pack_size} succeeded"
            )
            perf_rows = sum(
                1
                for t in ctrl.state.list_trials(name)
                for log in ctrl.obs_store.get_observation_log(t.name)
                if log.metric_name.startswith(PERF_PREFIX)
            )
            rendered = ctrl.metrics.render()
            stragglers = [
                e for e in ctrl.events.list(name) if e.reason == "GangStraggler"
            ]
            if stats_on:
                assert perf_rows > 0, "step stats on but no perf rows"
                assert "katib_step_seconds" in rendered
            else:
                assert perf_rows == 0, (
                    f"knob off but {perf_rows} perf rows written"
                )
                assert "katib_step_seconds" not in rendered
                assert "katib_trial_throughput" not in rendered
            return dt, stragglers
        finally:
            ctrl.close()
            if inject:
                del os.environ["KATIB_TPU_STEP_STATS_INJECT"]
            if prev is not None:
                os.environ["KATIB_TPU_STEP_STATS_INJECT"] = prev

    run_once(False)  # warmup
    passes = 2 if smoke else 3
    on_s, off_s = [], []
    for _ in range(passes):
        off_s.append(run_once(False)[0])
        on_s.append(run_once(True)[0])
    on, off = min(on_s), min(off_s)
    overhead_pct = (on - off) / off * 100.0
    # injected straggler: member 3 runs 8x slow — exactly one gang member
    # must cross the straggler_ratio*median line
    _, stragglers = run_once(True, inject="straggle=3@8.0")
    assert len(stragglers) == 1, (
        f"expected exactly 1 GangStraggler event, got {len(stragglers)}"
    )
    return {
        "pack_size": pack_size,
        "reports_per_member": reports,
        "passes": passes,
        "off_s": round(off, 4),
        "on_s": round(on, 4),
        "overhead_pct": round(overhead_pct, 2),
        "target_pct": 3.0,
        "within_target": overhead_pct < 3.0,
        "straggler_events": len(stragglers),
        "smoke": smoke,
    }


def _bench_telemetry_overhead(smoke: bool = False):
    """Resource telemetry (katib_tpu/telemetry.py): end-to-end trials/sec of
    an in-process experiment with ``runtime.telemetry`` on vs off. The
    target is <2% overhead when on (the per-report cost is one heartbeat
    dict store; the sampler itself ticks on its own thread) and ~0% when off
    (off IS the KATIB_TPU_TELEMETRY=0 path: every call site reduces to one
    boolean check). The on side runs the sampler at a 50ms interval — ~100x
    the production rate — so the measurement actually contains sampling
    work rather than an idle thread. Interleaved on/off passes, each side's
    best kept, same noise-shedding shape as tracing_overhead. ``smoke``
    trims the trial count for the tier-1 wiring test."""
    from katib_tpu.api.spec import (
        AlgorithmSpec, ExperimentSpec, FeasibleSpace, ObjectiveSpec,
        ObjectiveType, ParameterSpec, ParameterType, TrialTemplate,
    )
    from katib_tpu.config import KatibConfig
    from katib_tpu.controller.experiment import ExperimentController

    n_trials = 12 if smoke else int(os.environ.get("BENCH_TELEMETRY_TRIALS", "64"))
    reports = 20 if smoke else 100     # report() is the hottest heartbeat site
    work = 200 if smoke else 20000     # busy-work per step (see tracing bench:
    # an empty trial loop measures thread-scheduling noise, not telemetry)

    def trial_fn(assignments, ctx):
        x = float(assignments.get("x", "0.5"))
        for i in range(reports):
            acc = 0
            for j in range(work):
                acc += j & 7
            x = x * 0.999 + 1e-9 * acc
            ctx.report(score=x)

    counter = {"n": 0}

    def run_once(telemetry_on: bool) -> float:
        counter["n"] += 1
        cfg = KatibConfig()
        cfg.runtime.telemetry = telemetry_on
        cfg.runtime.telemetry_interval_seconds = 0.05  # stress rate, see above
        cfg.runtime.tracing = False       # isolate telemetry cost
        cfg.runtime.obslog_buffered = False
        ctrl = ExperimentController(
            root_dir=None, devices=list(range(8)), persist=False, config=cfg
        )
        name = f"telemetry-bench-{counter['n']}"
        spec = ExperimentSpec(
            name=name,
            parameters=[
                ParameterSpec(
                    "x", ParameterType.DOUBLE, FeasibleSpace(min="0.1", max="1.0")
                )
            ],
            objective=ObjectiveSpec(
                type=ObjectiveType.MAXIMIZE, objective_metric_name="score"
            ),
            algorithm=AlgorithmSpec("random"),
            trial_template=TrialTemplate(function=trial_fn),
            max_trial_count=n_trials,
            parallel_trial_count=8,
        )
        try:
            ctrl.create_experiment(spec)
            t0 = time.perf_counter()
            exp = ctrl.run(name, timeout=300)
            dt = time.perf_counter() - t0
            assert exp.status.trials_succeeded == n_trials, (
                f"{exp.status.trials_succeeded}/{n_trials} succeeded"
            )
            if telemetry_on:
                assert ctrl.telemetry.enabled
                if not smoke:
                    # the sampler really ran: the samples counter advanced
                    # (smoke passes can finish inside one 50ms tick)
                    assert "katib_telemetry_samples_total" in ctrl.metrics.render()
            else:
                assert not ctrl.telemetry.enabled
            return dt
        finally:
            ctrl.close()

    run_once(False)  # warmup: import + state costs off the timed passes
    passes = 2 if smoke else 3
    on_s, off_s = [], []
    for _ in range(passes):
        off_s.append(run_once(False))
        on_s.append(run_once(True))
    on, off = min(on_s), min(off_s)
    overhead_pct = (on - off) / off * 100.0
    return {
        "trials": n_trials,
        "reports_per_trial": reports,
        "sampler_interval_s": 0.05,
        "passes": passes,
        "off_s": round(off, 4),
        "on_s": round(on, 4),
        "off_trials_per_s": round(n_trials / off, 1),
        "on_trials_per_s": round(n_trials / on, 1),
        "overhead_pct": round(overhead_pct, 2),
        "target_pct": 2.0,
        "within_target": overhead_pct < 2.0,
        "smoke": smoke,
    }


def _bench_check_latency(smoke: bool = False):
    """Wall-clock of one full `katib-tpu check` pass over katib_tpu/
    (ISSUE 6 satellite): the analyzer gates every PR from a tier-1 test, so
    the pass itself must stay a few seconds at most or it gets turned off.
    Pure-AST — no JAX import, no backend — so smoke IS the full measurement
    (there is nothing to trim)."""
    import time as _time

    from katib_tpu.analysis.engine import check_paths

    repo = os.path.dirname(os.path.abspath(__file__))
    t0 = _time.perf_counter()
    findings, stats = check_paths(["katib_tpu"], repo_root=repo)
    elapsed = _time.perf_counter() - t0
    return {
        "files": stats["files"],
        "findings": len(findings),
        "suppressed": stats["suppressed"],
        "elapsed_s": round(elapsed, 3),
        "files_per_s": round(stats["files"] / elapsed, 1) if elapsed else None,
        "target_s": 5.0,
        "within_target": elapsed < 5.0,
        "smoke": smoke,
    }


def _bench_analyze_latency(smoke: bool = False):
    """Wall-clock of `katib-tpu analyze` over the two flagship workloads
    (ISSUE 7 satellite): mnist + transformer under their example search
    spaces. The analyzer sits on the admission path (HBM pre-flight) and
    the dispatch path consults its cache, so the full classification —
    baseline trace plus every corner trace — must stay under a few
    seconds. Measured post-import (jax import cost is the process's, not
    the analyzer's); ``smoke`` is the full measurement (abstract tracing
    has nothing to trim)."""
    import time as _time

    from katib_tpu.analysis.program import analyze_spec, clear_cache
    from katib_tpu.api.spec import load_experiment_document

    repo = os.path.dirname(os.path.abspath(__file__))
    results = {}
    total = 0.0
    for label, spec_file in (
        ("mnist", "examples/random.json"),
        ("transformer", "examples/distributed-lm.json"),
    ):
        with open(os.path.join(repo, spec_file)) as f:
            spec = load_experiment_document(f.read())
        clear_cache()
        t0 = _time.perf_counter()
        analysis = analyze_spec(spec)
        elapsed = _time.perf_counter() - t0
        total += elapsed
        assert analysis.analyzable, analysis.error
        results[label] = {
            "elapsed_s": round(elapsed, 3),
            "fingerprint": analysis.fingerprint,
            "classes": dict(analysis.classes),
            "flops": analysis.cost.flops,
            "peak_bytes": analysis.cost.peak_bytes,
        }
    return {
        "targets": results,
        "elapsed_s": round(total, 3),
        "target_s": 5.0,
        "within_target": total < 5.0,
        "smoke": smoke,
    }


# synthetic-compile-cost state for compile_amortization: a module cache
# standing in for the jit cache — the first cold trial of a group pays the
# simulated XLA compile, warm trials (handed the service's executable via
# ctx.compiled_program) skip it
_AMORT_COMPILED: dict = {}
_AMORT_COMPILE_COST_S = 1.0
_AMORT_STEPS = 5


def _amort_trial(assignments, ctx):
    import jax.numpy as jnp

    lr = jnp.float32(float(assignments.get("lr", "0.1")))
    warm = ctx is not None and ctx.compiled_program is not None
    if not warm and "amort" not in _AMORT_COMPILED:
        # inline compile: the synthetic stand-in for the 23-51s XLA compile
        # BENCH_r02/r04 measured (real CPU compiles of toy programs are
        # milliseconds — too small to measure amortization against)
        time.sleep(_AMORT_COMPILE_COST_S)
        _AMORT_COMPILED["amort"] = True
    val = float(lr)
    for _ in range(_AMORT_STEPS):
        if warm:
            val = float(ctx.compiled_program.executable(jnp.float32(val)))
        else:
            val = val * 0.5
        ctx.report(loss=val)


def _amort_probe(assignments):
    import jax
    import jax.numpy as jnp

    from katib_tpu.analysis.program import ProgramProbe

    av = jax.ShapeDtypeStruct((), jnp.float32)
    return ProgramProbe(fn=lambda lr: lr * 0.5, args=(av,), hyperparams={"lr": av})


_amort_trial.abstract_program = _amort_probe


def _bench_compile_amortization(smoke: bool = False):
    """AOT compile service amortization (ISSUE 8): e2e wall-clock of an
    N-trial runtime-scalar sweep, cold (compile service off — the first
    trial pays the compile inline, on the dispatch critical path) vs
    pre-warmed (service on; the compile ran on the worker pool before
    dispatch, trials receive the executable via ctx.compiled_program).
    Synthetic-compile-cost scenario: the inline compile is a sleep standing
    in for the 23-51s XLA compiles BENCH_r02/r04 measured, because a real
    CPU compile of a bench-sized program is milliseconds. Target: >=2x
    cold/warm on the e2e. ``smoke`` trims the trial count and the synthetic
    cost for the tier-1 wiring test."""
    global _AMORT_COMPILE_COST_S
    from katib_tpu.analysis import program as semantic
    from katib_tpu.api.spec import (
        AlgorithmSpec, ExperimentSpec, FeasibleSpace, ObjectiveSpec,
        ObjectiveType, ParameterSpec, ParameterType, TrialTemplate,
    )
    from katib_tpu.config import KatibConfig
    from katib_tpu.controller.experiment import ExperimentController

    n_trials = 6 if smoke else 16
    _AMORT_COMPILE_COST_S = 0.3 if smoke else 1.0
    counter = {"n": 0}

    def run_once(service_on: bool):
        from katib_tpu.compilesvc.service import clear_process_cache

        counter["n"] += 1
        _AMORT_COMPILED.clear()
        semantic.clear_cache()
        clear_process_cache()  # each side measures from a cold service
        cfg = KatibConfig()
        cfg.runtime.telemetry = False
        cfg.runtime.tracing = False
        cfg.runtime.obslog_buffered = False
        cfg.runtime.compile_service = service_on
        cfg.runtime.compile_gate_seconds = 10.0 if service_on else 0.0
        ctrl = ExperimentController(
            root_dir=None, devices=list(range(8)), persist=False, config=cfg
        )
        name = f"amort-{'warm' if service_on else 'cold'}-{counter['n']}"
        lrs = [format(0.05 * (i + 1), ".4f") for i in range(n_trials)]
        spec = ExperimentSpec(
            name=name,
            parameters=[
                ParameterSpec("lr", ParameterType.DISCRETE, FeasibleSpace(list=lrs))
            ],
            objective=ObjectiveSpec(
                type=ObjectiveType.MINIMIZE, objective_metric_name="loss"
            ),
            algorithm=AlgorithmSpec("grid"),
            trial_template=TrialTemplate(function=_amort_trial),
            max_trial_count=n_trials,
            parallel_trial_count=min(8, n_trials),
        )
        stats = {}
        try:
            ctrl.create_experiment(spec)
            if service_on:
                # pre-warm: wait (bounded) for the admission-time AOT
                # compile so the timed e2e contains zero compile cost —
                # the scenario the service exists to produce
                deadline = time.time() + 30
                while time.time() < deadline:
                    s = ctrl.compile_service.stats()
                    if s["compiled"] >= 1:
                        break
                    time.sleep(0.01)
            t0 = time.perf_counter()
            exp = ctrl.run(name, timeout=300)
            dt = time.perf_counter() - t0
            assert exp.status.trials_succeeded == n_trials, (
                f"{exp.status.trials_succeeded}/{n_trials} succeeded"
            )
            if service_on:
                stats = ctrl.compile_service.stats()
                assert stats["compiled"] >= 1, stats
            return dt, stats
        finally:
            ctrl.close()

    warm_s, svc_stats = run_once(True)
    cold_s, _ = run_once(False)
    speedup = cold_s / warm_s if warm_s > 0 else float("inf")
    return {
        "trials": n_trials,
        "synthetic_compile_cost_s": _AMORT_COMPILE_COST_S,
        "cold_s": round(cold_s, 4),
        "warm_s": round(warm_s, 4),
        "speedup": round(speedup, 3),
        "service_compiles": svc_stats.get("compiled", 0),
        "service_traces": svc_stats.get("traces", 0),
        "target_speedup": 2.0,
        "within_target": speedup >= 2.0,
        "smoke": smoke,
    }


def _bench_pbt_fused_throughput(smoke: bool = False):
    """Fused population loops (ISSUE 9): generations/sec of one
    lax.scan-fused PBT sweep vs the per-generation job-queue driver on the
    same ``simple_pbt`` workload, plus the fused-vs-stepwise lineage
    parity check (chunk=G vs chunk=1 of the identical program must match
    bit-for-bit under the fixed seed). Target: >=5x generations/sec on
    CPU — the legacy driver pays suggestion sync + dispatch walk + thread
    spawn + DB commits per generation, the fused sweep pays them once.
    ``smoke`` trims generation counts to wiring-check scale (no ratio
    assertion: sub-second walls are scheduler noise)."""
    import tempfile
    import time as _time

    import numpy as _np

    from katib_tpu.api import (
        AlgorithmSetting, AlgorithmSpec, ExperimentSpec, FeasibleSpace,
        ObjectiveSpec, ObjectiveType, ParameterSpec, ParameterType,
        TrialTemplate,
    )
    from katib_tpu.config import KatibConfig
    from katib_tpu.controller.experiment import ExperimentController
    from katib_tpu.models.simple_pbt import run_pbt_trial_packed
    from katib_tpu.runtime import population as pop

    population = 5
    # multiple of the default chunk (16) so the sweep reuses ONE compiled
    # scan program end to end (a ragged tail would compile a second)
    fused_gens = 6 if smoke else 32
    legacy_gens = 2 if smoke else 4  # the slow side: bounded on purpose

    def spec_for(name, fused: bool, gens: int, root: str):
        settings = [
            AlgorithmSetting("n_population", str(population)),
            AlgorithmSetting("truncation_threshold", "0.4"),
            AlgorithmSetting("random_state", "13"),
            AlgorithmSetting(
                "suggestion_trial_dir", os.path.join(root, "pbt-state")
            ),
        ]
        if fused:
            settings.append(AlgorithmSetting("fused_generations", str(gens)))
        return ExperimentSpec(
            name=name,
            parameters=[
                ParameterSpec(
                    "lr", ParameterType.DOUBLE,
                    FeasibleSpace(min="0.0001", max="0.02"),
                )
            ],
            objective=ObjectiveSpec(
                type=ObjectiveType.MAXIMIZE,
                objective_metric_name="Validation-accuracy",
            ),
            algorithm=AlgorithmSpec("pbt", algorithm_settings=settings),
            trial_template=TrialTemplate(function=run_pbt_trial_packed),
            max_trial_count=population * gens,
            parallel_trial_count=population,
        )

    def run_once(fused: bool, gens: int):
        root = tempfile.mkdtemp(prefix="bench-fusedpop-")
        cfg = KatibConfig()
        cfg.runtime.fused_population = fused
        cfg.runtime.telemetry = False
        cfg.runtime.tracing = False
        c = ExperimentController(
            root_dir=root, devices=list(range(population)), config=cfg
        )
        try:
            name = f"fusedpop-{'fused' if fused else 'legacy'}"
            spec = spec_for(name, fused, gens, root)
            c.create_experiment(spec)
            if fused:
                # let the admission prewarm land so the measured wall is the
                # steady-state sweep, not the one-time AOT compile (the
                # legacy side's jit cache is equally warm after gen 0)
                key = pop.fused_group_key(spec, min(16, gens))
                deadline = _time.time() + 60
                while _time.time() < deadline:
                    if c.compile_service is None or (
                        c.compile_service.warm_executable_for_key(key)
                        is not None
                    ):
                        break
                    _time.sleep(0.02)
            t0 = _time.time()
            exp = c.run(name, timeout=600)
            wall = _time.time() - t0
            assert exp.status.is_succeeded, exp.status.message
            if fused:
                completed = gens
            else:
                # one legacy "generation" = one K-trial population round
                # (suggestion sync + dispatch + K reports); the PBT lineage
                # label lags this by a round, so count dispatched rounds
                completed = len(c.state.list_trials(name)) // population
            return completed / wall, completed, wall
        finally:
            c.close()

    legacy_rate, legacy_done, legacy_wall = run_once(False, legacy_gens)
    fused_rate, fused_done, fused_wall = run_once(True, fused_gens)

    # lineage parity: the fused scan vs the per-generation (chunk=1) drive
    # of the SAME program must agree bit-for-bit — score, best/median, and
    # the exploit/explore lineage record
    parity_spec = spec_for("fusedpop-parity", True, 8, tempfile.mkdtemp())
    program = pop.build_program(parity_spec)
    _, fused_ys = pop.run_generations(program, 8)
    _, step_ys = pop.run_generations(program, 8, chunk=1)
    parity = all(
        _np.array_equal(fused_ys[k], step_ys[k]) for k in fused_ys
    )

    speedup = fused_rate / legacy_rate if legacy_rate else float("inf")
    return {
        "population": population,
        "fused_generations": fused_done,
        "legacy_generations": legacy_done,
        "fused_gen_per_s": round(fused_rate, 2),
        "legacy_gen_per_s": round(legacy_rate, 2),
        "fused_wall_s": round(fused_wall, 3),
        "legacy_wall_s": round(legacy_wall, 3),
        "speedup": round(speedup, 2),
        "lineage_bit_identical": parity,
        "target_speedup": 5.0,
        "within_target": speedup >= 5.0,
        "smoke": smoke,
    }


def _bench_suggestion_throughput(smoke: bool = False):
    """Vectorized suggestion plane (ISSUE 10): candidates/sec of the
    batched jitted TPE / CMA-ES / BO kernels (suggest/vectorized.py) vs the
    legacy NumPy suggesters on identical seeded histories, with parity
    asserted — the vectorized path must reproduce the legacy selections
    (same rng call sequence, f64 refinement) within fp tolerance.

    Honesty note on the speedup target: the ≥5x goal assumes an
    accelerator backend (the kernels are single fused batched programs —
    exactly the shape TPUs eat). On the 1-core CI box XLA's CPU elementwise
    throughput is only ~2x NumPy's staged pipelines and the GP solves race
    OpenBLAS, so CPU-measured speedups land ~1.5-2x (BO's flop structure —
    ONE factorization + half-triangle batched solves vs per-pick refits —
    is a 4x flop cut that shows at larger histories). The bench records
    the measured ratio and the target verdict rather than asserting a
    number this box cannot honestly produce; the floor assertion is that
    the vectorized path is parity-exact and not slower."""
    import time as _time

    import numpy as _np

    from katib_tpu.api import (
        AlgorithmSetting, AlgorithmSpec, ExperimentSpec, FeasibleSpace,
        Metric, Observation, ObjectiveSpec, ObjectiveType,
        ParameterAssignment, ParameterSpec, ParameterType, Trial,
        TrialCondition, TrialTemplate,
    )
    from katib_tpu.suggest import vectorized
    from katib_tpu.suggest.base import SuggestionRequest, create

    def spec_for(algo, settings, dim):
        return ExperimentSpec(
            name="suggest-bench",
            parameters=[
                ParameterSpec(
                    f"x{i:02d}", ParameterType.DOUBLE,
                    FeasibleSpace(min="0.0", max="1.0"),
                )
                for i in range(dim)
            ],
            objective=ObjectiveSpec(
                type=ObjectiveType.MINIMIZE, objective_metric_name="loss"
            ),
            algorithm=AlgorithmSpec(
                algo,
                algorithm_settings=[
                    AlgorithmSetting(k, str(v)) for k, v in settings.items()
                ],
            ),
            trial_template=TrialTemplate(function=lambda a, c: None),
            max_trial_count=100000,
            parallel_trial_count=64,
        )

    def history(n, dim, labels_fn=None, seed=0):
        r = _np.random.default_rng(seed)
        out = []
        for i in range(n):
            a = {
                f"x{j:02d}": round(float(r.random()), 8) for j in range(dim)
            }
            v = round(float(sum((x - 0.3) ** 2 for x in a.values())), 8)
            t = Trial(
                name=f"t{i:04d}",
                experiment_name="suggest-bench",
                parameter_assignments=[
                    ParameterAssignment(k, str(x)) for k, x in a.items()
                ],
                labels=labels_fn(i) if labels_fn else {},
            )
            t.observation = Observation(
                metrics=[
                    Metric(name="loss", min=str(v), max=str(v), latest=str(v))
                ]
            )
            t.condition = TrialCondition.SUCCEEDED
            t.start_time = 1.0
            out.append(t)
        return out

    if smoke:
        configs = [
            ("tpe", {"random_state": 7, "n_ei_candidates": 16,
                     "n_startup_trials": 8}, 4, 30, 4, None),
            ("cmaes", {"random_state": 7, "popsize": 6}, 4, 24, 4,
             lambda i: {"cmaes-generation": str(i // 6)}),
            ("bayesianoptimization",
             {"random_state": 7, "acq_func": "gp_hedge",
              "n_initial_points": 8}, 4, 24, 3,
             lambda i: {"bo-acq": ["ei", "pi", "lcb"][i % 3]}),
        ]
        rounds = 1
    else:
        configs = [
            ("tpe", {"random_state": 7, "n_ei_candidates": 64}, 16, 256, 32,
             None),
            ("cmaes", {"random_state": 7, "popsize": 8}, 8, 512, 16,
             lambda i: {"cmaes-generation": str(i // 8)}),
            ("bayesianoptimization",
             {"random_state": 7, "acq_func": "gp_hedge"}, 8, 384, 32,
             lambda i: {"bo-acq": ["ei", "pi", "lcb"][i % 3]}),
        ]
        rounds = 3

    prev_enabled = vectorized.enabled()
    results = {}
    try:
        for algo, settings, dim, hist_n, batch, labels_fn in configs:
            trials = history(hist_n, dim, labels_fn)
            spec = spec_for(algo, settings, dim)
            request = SuggestionRequest(
                experiment=spec, trials=trials, current_request_number=batch
            )
            suggester = create(algo)
            walls = {}
            picks = {}
            for vec in (False, True):
                vectorized.set_enabled(vec)
                suggester.get_suggestions(request)  # warmup / compile
                t0 = _time.perf_counter()
                for _ in range(rounds):
                    reply = suggester.get_suggestions(request)
                walls[vec] = (_time.perf_counter() - t0) / rounds
                picks[vec] = _np.array(
                    [
                        [float(v) for _, v in sorted(a.assignments_dict().items())]
                        for a in reply.assignments
                    ]
                )
            parity_err = float(_np.abs(picks[False] - picks[True]).max())
            assert parity_err < 1e-6, (
                f"{algo}: vectorized selections diverged from the legacy "
                f"oracle by {parity_err}"
            )
            speedup = walls[False] / walls[True]
            if not smoke:
                assert speedup > 1.0, (
                    f"{algo}: vectorized path slower than legacy "
                    f"({walls[True]*1e3:.1f}ms vs {walls[False]*1e3:.1f}ms)"
                )
            results[algo] = {
                "dim": dim,
                "history": hist_n,
                "batch": batch,
                "legacy_cands_per_s": round(batch / walls[False], 1),
                "vectorized_cands_per_s": round(batch / walls[True], 1),
                "legacy_ms": round(walls[False] * 1e3, 2),
                "vectorized_ms": round(walls[True] * 1e3, 2),
                "speedup": round(speedup, 2),
                "parity_err": parity_err,
                "within_target": speedup >= 5.0,
            }
    finally:
        vectorized.set_enabled(prev_enabled)
    return {
        "algos": results,
        "target_speedup": 5.0,
        "target_note": (
            "target assumes an accelerator backend; 1-core CPU measures the "
            "fusion + flop-cut share only (see docs/suggestion-plane.md)"
        ),
        "parity_exact": all(r["parity_err"] < 1e-6 for r in results.values()),
        "smoke": smoke,
    }


def _bench_suggestion_pipeline_latency(smoke: bool = False):
    """Async pipelined suggestion (ISSUE 10): mean scheduler-observed
    `suggestion` span (the PR 4 span around sync_assignments in the
    reconcile loop) on a TPE sweep with the prefetch worker on vs the
    inline legacy path, plus the no-duplicate/no-loss integrity check.
    Target: >=3x lower mean span with async on. The legacy NumPy suggester
    (vector_suggest off) runs on BOTH sides so the ratio isolates the
    pipeline, not the kernels."""
    import tempfile
    import time as _time

    from katib_tpu.api import (
        AlgorithmSetting, AlgorithmSpec, ExperimentSpec, FeasibleSpace,
        ObjectiveSpec, ObjectiveType, ParameterSpec, ParameterType,
        TrialTemplate,
    )
    from katib_tpu.config import KatibConfig
    from katib_tpu.controller.experiment import ExperimentController
    from katib_tpu.tracing import SPAN_DURATION_METRIC

    n_trials = 8 if smoke else 64
    candidates = 256 if smoke else 2048  # weight the inline compute
    # Pipelining needs the trial window to cover the precompute, as real
    # sweeps do (trials run minutes; suggestion batches take ms-s). The
    # sleep is idle time, so on the 1-core box the prefetch worker
    # computes in it without contending with trial work.
    trial_seconds = 0.02 if smoke else 0.06

    def trial_fn(assignments, ctx):
        x = float(assignments["x0"])
        _time.sleep(trial_seconds)
        ctx.report(loss=(x - 0.4) ** 2)

    def spec_for(name):
        return ExperimentSpec(
            name=name,
            parameters=[
                ParameterSpec(
                    f"x{i}", ParameterType.DOUBLE,
                    FeasibleSpace(min="0.0", max="1.0"),
                )
                for i in range(6)
            ],
            objective=ObjectiveSpec(
                type=ObjectiveType.MINIMIZE, objective_metric_name="loss"
            ),
            algorithm=AlgorithmSpec(
                "tpe",
                algorithm_settings=[
                    AlgorithmSetting("random_state", "11"),
                    AlgorithmSetting("n_startup_trials", "4"),
                    AlgorithmSetting("n_ei_candidates", str(candidates)),
                ],
            ),
            trial_template=TrialTemplate(function=trial_fn),
            max_trial_count=n_trials,
            parallel_trial_count=4,
        )

    def run_once(async_on: bool):
        root = tempfile.mkdtemp(prefix="bench-suggest-pipe-")
        cfg = KatibConfig()
        cfg.runtime.async_suggest = async_on
        cfg.runtime.vector_suggest = False  # isolate the pipeline
        cfg.runtime.telemetry = False
        cfg.runtime.compile_service = False
        c = ExperimentController(
            root_dir=root, devices=list(range(4)), config=cfg
        )
        try:
            name = f"pipe-{'async' if async_on else 'inline'}"
            c.create_experiment(spec_for(name))
            t0 = _time.time()
            exp = c.run(name, timeout=600)
            wall = _time.time() - t0
            assert exp.status.is_succeeded, exp.status.message
            trials = c.state.list_trials(name)
            names = [t.name for t in trials]
            # integrity: zero duplicate or lost assignments
            assert len(names) == len(set(names)) == n_trials, (
                len(names), len(set(names)))
            key = (SPAN_DURATION_METRIC, (("stage", "suggestion"),))
            hist = c.metrics._histograms.get(key)
            mean_span = (hist.sum / hist.count) if hist and hist.count else 0.0
            hits = sum(
                v for (metric, _), v in c.metrics._counters.items()
                if metric == "katib_suggestion_buffer_ready_total"
            )
            return mean_span, wall, hits
        finally:
            c.close()

    inline_span, inline_wall, _ = run_once(False)
    async_span, async_wall, async_hits = run_once(True)
    ratio = inline_span / async_span if async_span else float("inf")
    if not smoke:
        assert async_hits > 0, "async sweep never hit the prefetch buffer"
        assert ratio >= 3.0, (
            f"mean suggestion span only improved {ratio:.1f}x "
            f"({inline_span*1e3:.2f}ms -> {async_span*1e3:.2f}ms)"
        )
    return {
        "trials": n_trials,
        "inline_mean_span_ms": round(inline_span * 1e3, 3),
        "async_mean_span_ms": round(async_span * 1e3, 3),
        "span_ratio": round(ratio, 2),
        "inline_wall_s": round(inline_wall, 2),
        "async_wall_s": round(async_wall, 2),
        "async_buffer_hits": async_hits,
        "target_ratio": 3.0,
        "within_target": ratio >= 3.0,
        "smoke": smoke,
    }


def _bench_asha_device_seconds(smoke: bool = False):
    """Native multi-fidelity search (ISSUE 11): ASHA vs a flat TPE sweep
    over the same search space, both reaching the target objective. The
    cost unit is deterministic device-work — one training epoch (one
    reported row) — so the ratio is free of controller-overhead noise:
    ASHA admits every configuration at the bottom rung and only survivors
    resume (checkpoint-promoted, never retrained from scratch) at higher
    fidelity, while the flat sweep pays the full budget for every config.
    Target: >=5x fewer device-epochs, zero lost observations across
    promotions (fold-index totals byte-identical to a row scan, every
    epoch curve continuous)."""
    import math
    import tempfile

    from katib_tpu.api import (
        AlgorithmSetting, AlgorithmSpec, ExperimentSpec, FeasibleSpace,
        ObjectiveSpec, ObjectiveType, ParameterSpec, ParameterType,
        TrialTemplate,
    )
    from katib_tpu.config import KatibConfig
    from katib_tpu.controller.experiment import ExperimentController
    from katib_tpu.db.store import fold_observation

    n_configs = 9 if smoke else 27
    r_max = 9 if smoke else 27   # eta=3 ladder: 1, 3, 9(, 27)
    curve_max = 1.0 * (1.0 - math.exp(-r_max / 8.0))
    target = 0.80 * curve_max    # reachable only by a good x at high budget

    def asha_fn(assignments, ctx):
        x = float(assignments["x"])
        budget = int(float(assignments["epochs"]))
        store = ctx.checkpoint_store()
        restored = store.restore()
        start = int(restored["epoch"]) + 1 if restored else 1
        for epoch in range(start, budget + 1):
            score = x * (1.0 - math.exp(-epoch / 8.0))
            store.save(epoch, {"epoch": epoch})
            ctx.report(score=score, epoch=epoch)

    def flat_fn(assignments, ctx):
        x = float(assignments["x"])
        for epoch in range(1, r_max + 1):
            ctx.report(score=x * (1.0 - math.exp(-epoch / 8.0)), epoch=epoch)

    def run_once(name, algorithm, settings, fn, params):
        root = tempfile.mkdtemp(prefix="bench-asha-")
        cfg = KatibConfig()
        cfg.runtime.telemetry = False
        cfg.runtime.compile_service = False
        c = ExperimentController(root_dir=root, devices=list(range(4)), config=cfg)
        try:
            spec = ExperimentSpec(
                name=name,
                parameters=params,
                objective=ObjectiveSpec(
                    type=ObjectiveType.MAXIMIZE, objective_metric_name="score"
                ),
                algorithm=AlgorithmSpec(algorithm, algorithm_settings=settings),
                trial_template=TrialTemplate(function=fn),
                max_trial_count=n_configs,
                parallel_trial_count=4,
            )
            c.create_experiment(spec)
            t0 = time.time()
            exp = c.run(name, timeout=600)
            wall = time.time() - t0
            assert exp.status.is_succeeded, exp.status.message
            trials = c.state.list_trials(name)
            epochs = 0
            best = float("-inf")
            lost = 0
            for t in trials:
                rows = c.obs_store.get_observation_log(t.name, metric_name="epoch")
                steps = [int(float(r.value)) for r in rows]
                epochs += len(steps)
                # continuity: promotions must extend the SAME curve — a gap
                # or duplicate means observations were lost or re-reported
                if steps != list(range(1, len(steps) + 1)):
                    lost += 1
                fold = c.obs_store.folded(t.name, ["score", "epoch"]).to_dict()
                rescan = fold_observation(
                    c.obs_store.get_observation_log(t.name), ["score", "epoch"]
                ).to_dict()
                if fold != rescan:
                    lost += 1
                m = next(
                    (m for m in c.obs_store.folded(t.name, ["score"]).metrics), None
                )
                if m is not None and m.max not in ("unavailable",):
                    try:
                        best = max(best, float(m.max))
                    except ValueError:
                        pass
            promotions = sum(
                1 for e in c.events.list(name) if e.reason == "RungPromoted"
            )
            return {
                "configs": len(trials),
                "device_epochs": epochs,
                "best": best,
                "lost": lost,
                "wall_s": round(wall, 2),
                "promotions": promotions,
            }
        finally:
            c.close()

    asha = run_once(
        "bench-asha",
        "asha",
        [
            AlgorithmSetting("eta", "3"),
            AlgorithmSetting("resource_name", "epochs"),
            AlgorithmSetting("random_state", "17"),
        ],
        asha_fn,
        [
            ParameterSpec("x", ParameterType.DOUBLE, FeasibleSpace(min="0", max="1")),
            ParameterSpec("epochs", ParameterType.INT, FeasibleSpace(min="1", max=str(r_max))),
        ],
    )
    flat = run_once(
        "bench-flat-tpe",
        "tpe",
        [
            AlgorithmSetting("random_state", "17"),
            AlgorithmSetting("n_startup_trials", "4"),
        ],
        flat_fn,
        [ParameterSpec("x", ParameterType.DOUBLE, FeasibleSpace(min="0", max="1"))],
    )
    ratio = (
        flat["device_epochs"] / asha["device_epochs"]
        if asha["device_epochs"]
        else float("inf")
    )
    assert asha["lost"] == 0 and flat["lost"] == 0, (asha["lost"], flat["lost"])
    assert asha["configs"] == flat["configs"] == n_configs
    assert asha["promotions"] > 0, "ASHA sweep never promoted a trial"
    reached = asha["best"] >= target and flat["best"] >= target
    if not smoke:
        assert reached, (asha["best"], flat["best"], target)
        assert ratio >= 5.0, (
            f"ASHA used {asha['device_epochs']} device-epochs vs flat "
            f"{flat['device_epochs']} — only {ratio:.1f}x"
        )
    return {
        "configs": n_configs,
        "ladder_max_resource": r_max,
        "asha_device_epochs": asha["device_epochs"],
        "flat_device_epochs": flat["device_epochs"],
        "device_seconds_ratio": round(ratio, 2),
        "asha_best": round(asha["best"], 6),
        "flat_best": round(flat["best"], 6),
        "target_objective": round(target, 6),
        "target_reached": reached,
        "promotions": asha["promotions"],
        "lost_observations": asha["lost"] + flat["lost"],
        "asha_wall_s": asha["wall_s"],
        "flat_wall_s": flat["wall_s"],
        "target_ratio": 5.0,
        "within_target": ratio >= 5.0,
        "smoke": smoke,
    }


def _bench_bohb_convergence(smoke: bool = False):
    """Model-based multi-fidelity (ISSUE 13): BOHB vs PR 11's ASHA on the
    same 27-config ladder scenario, plus the dwell-window packed-promotion
    dispatch assertion, per-bracket device-epoch accounting, and the
    cold-vs-warm transfer assertion.

    The cost unit is deterministic device-work (one epoch = one reported
    row) and the headline is epochs-to-target: replaying every score row
    in timestamp order, how many device-epochs the sweep consumed before
    the target objective first appeared. Both sweeps run the identical
    ladder (eta=3, 1/3/9/27) over the identical space, so the difference
    is purely where the admissions landed: BOHB's per-rung KDE
    concentrates on the good region once d+2 observations exist, ASHA
    stays uniform. Target: BOHB <= 0.7x ASHA's epochs-to-target, zero
    lost observations, and rung-1+ promotions dispatching as
    ceil(promotions/pack_capacity) vmapped packs instead of one group per
    promotion."""
    import math
    import tempfile

    import numpy as np

    from katib_tpu.api import (
        AlgorithmSetting, AlgorithmSpec, ExperimentSpec, FeasibleSpace,
        ObjectiveSpec, ObjectiveType, ParameterSpec, ParameterType,
        TrialTemplate,
    )
    from katib_tpu.api.spec import TrialResources
    from katib_tpu.config import KatibConfig
    from katib_tpu.controller.experiment import ExperimentController
    from katib_tpu.controller.multifidelity import BRACKET_LABEL, RUNG_LABEL
    from katib_tpu.db.store import fold_observation

    n_configs = 9 if smoke else 27
    r_max = 9 if smoke else 27   # eta=3 ladder: 1, 3, 9(, 27)
    curve_max = 1.0 * (1.0 - math.exp(-r_max / 8.0))
    # reachable only by a good x at high fidelity: rung 2 needs x >= ~0.92,
    # the top rung needs x >= ~0.64 — uniform sampling pays most of the
    # ladder first, the KDE model concentrates there within a few batches
    target = (0.81 if smoke else 0.92) * curve_max * 0.7

    def curve_fn(assignments, ctx):
        x = float(assignments["x"])
        budget = int(float(assignments["epochs"]))
        store = ctx.checkpoint_store()
        restored = store.restore()
        start = int(restored["epoch"]) + 1 if restored else 1
        for epoch in range(start, budget + 1):
            score = x * (1.0 - math.exp(-epoch / 8.0))
            store.save(epoch, {"epoch": epoch})
            ctx.report(score=score, epoch=epoch)

    def pack_curve_fn(assignments, ctx):
        """Dual-mode (solo/packed) variant with per-member checkpoints, so
        packed promotion stints resume exactly like solo ones."""
        from katib_tpu.runtime.checkpoints import CheckpointStore
        from katib_tpu.runtime.packed import (
            population_of, report_population, uniform_param,
        )

        pop = population_of(assignments)
        budget = int(uniform_param(pop, "epochs", 1))
        xs = pop["x"]
        if hasattr(ctx, "pack_size"):
            dirs = [
                cd or wd for cd, wd in zip(ctx.checkpoint_dirs, ctx.workdirs)
            ]
            stores = [CheckpointStore(d) for d in dirs]
        else:
            stores = [ctx.checkpoint_store()]
        restored = [s.restore() for s in stores]
        start = min(int(r["epoch"]) + 1 if r else 1 for r in restored)
        for epoch in range(start, budget + 1):
            for s in stores:
                s.save(epoch, {"epoch": epoch})
            score = xs * (1.0 - np.exp(-epoch / 8.0))
            report_population(
                ctx, score=score, epoch=np.full(len(xs), float(epoch))
            )

    def spec_for(name, algorithm, fn, *, eta=3, max_resource=r_max,
                 max_trials=n_configs, parallel=2, extra=()):
        return ExperimentSpec(
            name=name,
            parameters=[
                ParameterSpec("x", ParameterType.DOUBLE, FeasibleSpace(min="0", max="1")),
                ParameterSpec(
                    "epochs", ParameterType.INT,
                    FeasibleSpace(min="1", max=str(max_resource)),
                ),
            ],
            objective=ObjectiveSpec(
                type=ObjectiveType.MAXIMIZE, objective_metric_name="score"
            ),
            algorithm=AlgorithmSpec(
                algorithm,
                algorithm_settings=[
                    AlgorithmSetting("eta", str(eta)),
                    AlgorithmSetting("resource_name", "epochs"),
                    AlgorithmSetting("random_state", "17"),
                    *extra,
                ],
            ),
            trial_template=TrialTemplate(function=fn),
            max_trial_count=max_trials,
            parallel_trial_count=parallel,
        )

    # BOHB settings for the race: a slightly sharper model than the
    # defaults (the defaults stay the paper's; the bench pins its scenario)
    bohb_extra = (
        AlgorithmSetting("random_fraction", "0.15"),
        AlgorithmSetting("gamma", "0.15"),
    )

    def controller(root, **overrides):
        cfg = KatibConfig()
        cfg.runtime.telemetry = False
        cfg.runtime.compile_service = False
        for k, v in overrides.items():
            setattr(cfg.runtime, k, v)
        return ExperimentController(
            root_dir=root, devices=list(range(4)), config=cfg
        )

    def audit(c, name):
        """(epochs_to_target, total_epochs, lost, promotions) of one run."""
        rows = []
        total = 0
        lost = 0
        for t in c.state.list_trials(name):
            logs = c.obs_store.get_observation_log(t.name, metric_name="epoch")
            steps = [int(float(r.value)) for r in logs]
            total += len(steps)
            if steps != list(range(1, len(steps) + 1)):
                lost += 1  # a promotion lost or re-reported rows
            fold = c.obs_store.folded(t.name, ["score", "epoch"]).to_dict()
            rescan = fold_observation(
                c.obs_store.get_observation_log(t.name), ["score", "epoch"]
            ).to_dict()
            if fold != rescan:
                lost += 1
            rows.extend(
                (r.timestamp, float(r.value))
                for r in c.obs_store.get_observation_log(
                    t.name, metric_name="score"
                )
            )
        rows.sort()
        to_target = next(
            (i + 1 for i, (_, s) in enumerate(rows) if s >= target), None
        )
        promotions = sum(
            1 for e in c.events.list(name) if e.reason == "RungPromoted"
        )
        return to_target, total, lost, promotions

    def race(algorithm, extra=()):
        root = tempfile.mkdtemp(prefix="bench-bohb-")
        c = controller(root)
        try:
            name = f"race-{algorithm}"
            c.create_experiment(spec_for(name, algorithm, curve_fn, extra=extra))
            exp = c.run(name, timeout=600)
            assert exp.status.is_succeeded, exp.status.message
            return audit(c, name)
        finally:
            c.close()

    asha_to, asha_total, asha_lost, _ = race("asha")
    bohb_to, bohb_total, bohb_lost, bohb_promos = race("bohb", bohb_extra)
    if not smoke:
        # whether a sweep crosses at all hinges on its one top-rung stint;
        # at the 27-config size that is robust, at the 9-config smoke size
        # it races async-promotion interleaving — so crossing (like every
        # other timing claim) is asserted only at full size
        assert asha_to is not None and bohb_to is not None, (asha_to, bohb_to)
    assert bohb_promos > 0, "BOHB sweep never promoted a trial"
    ratio = (bohb_to / asha_to) if (asha_to and bohb_to) else None

    # -- packed promotions under the dwell window ----------------------------
    pack_k = 4
    # the window only has to outlast the (trivial) sweep: the drain rule
    # flushes at the last boundary, so a generous value costs no wall time
    # but keeps a loaded CI box from splitting the batch mid-sweep
    root = tempfile.mkdtemp(prefix="bench-bohb-pack-")
    c = controller(root, promotion_dwell_seconds=30.0)
    try:
        spec = spec_for(
            "promo-pack", "asha", pack_curve_fn, eta=2, max_resource=2,
            max_trials=8, parallel=4,
        )
        spec.trial_template.resources = TrialResources(pack_size=pack_k)
        c.create_experiment(spec)
        exp = c.run("promo-pack", timeout=300)
        assert exp.status.is_succeeded, exp.status.message
        trials = c.state.list_trials("promo-pack")
        promoted = {
            t.name for t in trials if int(t.labels.get(RUNG_LABEL, "0")) > 0
        }
        events = c.events.list("promo-pack")
        promotions = sum(1 for e in events if e.reason == "RungPromoted")
        batched = [e for e in events if e.reason == "PromotionBatched"]
        promo_groups = [
            e for e in events
            if e.reason == "PackFormed"
            and set(e.message.split(": ", 1)[1].split(", ")) <= promoted
        ]
        expected_groups = math.ceil(promotions / pack_k)
        # the headline dispatch-count assertion: rung-1 promotions form
        # ceil(promotions/pack_capacity) vmapped packs, not one dispatch
        # group per promotion
        assert promotions == len(promoted) == 4, (promotions, promoted)
        assert len(batched) >= 1, "dwell window never batched promotions"
        assert len(promo_groups) == expected_groups < promotions, (
            len(promo_groups), expected_groups, promotions,
        )
        pack_result = {
            "promotions": promotions,
            "pack_capacity": pack_k,
            "dispatch_groups": len(promo_groups),
            "expected_groups": expected_groups,
            "batched_events": len(batched),
        }
    finally:
        c.close()

    # -- per-bracket device-epoch accounting ---------------------------------
    root = tempfile.mkdtemp(prefix="bench-bohb-brackets-")
    c = controller(root)
    try:
        c.create_experiment(
            spec_for(
                "brackets", "bohb", curve_fn, eta=2, max_resource=4,
                max_trials=12, parallel=4,
                extra=(AlgorithmSetting("brackets", "2"),),
            )
        )
        exp = c.run("brackets", timeout=300)
        assert exp.status.is_succeeded, exp.status.message
        per_bracket: dict = {}
        for t in c.state.list_trials("brackets"):
            b = t.labels.get(BRACKET_LABEL, "0")
            rows = c.obs_store.get_observation_log(t.name, metric_name="epoch")
            per_bracket[b] = per_bracket.get(b, 0) + len(rows)
        # regressions in any one bracket stay visible, not averaged away
        assert set(per_bracket) == {"0", "1"} and all(
            v > 0 for v in per_bracket.values()
        ), per_bracket
    finally:
        c.close()

    # -- cold vs warm (PR 10 history index into the rung-0 KDE) --------------
    root = tempfile.mkdtemp(prefix="bench-bohb-warm-")
    c = controller(root, warm_start=True)
    try:
        c.create_experiment(
            spec_for("bohb-cold", "bohb", curve_fn, extra=bohb_extra)
        )
        exp = c.run("bohb-cold", timeout=600)
        assert exp.status.is_succeeded, exp.status.message
        cold_to, _, cold_lost, _ = audit(c, "bohb-cold")
        cold_first = [
            float(t.assignments_dict()["x"])
            for t in c.state.list_trials("bohb-cold")[:2]
        ]
        c.create_experiment(
            spec_for("bohb-warm", "bohb", curve_fn, extra=bohb_extra)
        )
        exp = c.run("bohb-warm", timeout=600)
        assert exp.status.is_succeeded, exp.status.message
        warm_to, _, warm_lost, _ = audit(c, "bohb-warm")
        warm_first = [
            float(t.assignments_dict()["x"])
            for t in c.state.list_trials("bohb-warm")[:2]
        ]
        warm_applied = any(
            e.reason == "WarmStartApplied" for e in c.events.list("bohb-warm")
        )
        assert warm_applied, "warm experiment never received priors"
        # the priors arm the rung-0 model from batch 1: the warm first
        # batch is model-based, not the cold run's uniform draw
        assert warm_first != cold_first, (warm_first, cold_first)
        if not smoke:
            # cold-vs-warm race: the warm run reaches the target no slower
            # (20% slack absorbs async-promotion interleaving noise; the
            # smoke ladder is too short for any timing claim)
            assert warm_to is not None and warm_to <= cold_to * 1.2, (
                warm_to, cold_to,
            )
    finally:
        c.close()

    lost = asha_lost + bohb_lost + cold_lost + warm_lost
    assert lost == 0, lost
    if not smoke:
        assert ratio <= 0.7, (
            f"BOHB took {bohb_to} device-epochs to the target vs ASHA's "
            f"{asha_to} — ratio {ratio:.2f} > 0.7"
        )
    return {
        "configs": n_configs,
        "ladder_max_resource": r_max,
        "target_objective": round(target, 6),
        "asha_epochs_to_target": asha_to,
        "bohb_epochs_to_target": bohb_to,
        "asha_total_epochs": asha_total,
        "bohb_total_epochs": bohb_total,
        "epochs_to_target_ratio": None if ratio is None else round(ratio, 3),
        "bohb_promotions": bohb_promos,
        "promotion_pack": pack_result,
        "per_bracket_device_epochs": per_bracket,
        "cold_epochs_to_target": cold_to,
        "warm_epochs_to_target": warm_to,
        "warm_start_applied": warm_applied,
        "lost_observations": lost,
        "target_ratio": 0.7,
        "within_target": ratio is not None and ratio <= 0.7,
        "smoke": smoke,
    }


def _bench_device_chaos_recovery(smoke: bool = False):
    """Supervised device plane under injected faults (ISSUE 12): the same
    sweep runs fault-free and then with 1 wedged backend probe + 2
    mid-sweep device revocations (utils/chaos.py, deterministic schedule).
    The chaos run must complete with ZERO lost observations (every trial's
    epoch curve continuous 1..E), every preempted trial resuming —
    checkpointed ones bit-identically to the fault-free run — and e2e
    wall-clock <= 1.5x fault-free. The wedged probe additionally must cost
    one bounded attempt, not a 150s round."""
    import tempfile

    from katib_tpu.api import (
        AlgorithmSetting, AlgorithmSpec, ExperimentSpec, FeasibleSpace,
        ObjectiveSpec, ObjectiveType, ParameterSpec, ParameterType,
        TrialTemplate,
    )
    from katib_tpu.config import KatibConfig
    from katib_tpu.controller import deviceplane
    from katib_tpu.controller.experiment import ExperimentController
    from katib_tpu.utils import backend as backend_mod
    from katib_tpu.utils import chaos

    n_trials = 8 if smoke else 24
    epochs = 6
    n_devices = 8

    def trial_fn(assignments, ctx):
        x = float(assignments["x"])
        store = ctx.checkpoint_store()
        restored = store.restore()
        start = int(restored["epoch"]) + 1 if restored else 1
        for epoch in range(start, epochs + 1):
            # deterministic curve: resume-from-checkpoint and clean re-run
            # both reproduce it exactly, so "bit-identical" is checkable
            score = x * (1.0 - 0.8 ** epoch)
            time.sleep(0.002)
            # checkpoint BEFORE report: a preemption raised inside report()
            # then loses nothing (the row is written before the unwind)
            store.save(epoch, {"epoch": epoch})
            ctx.report(score=score, epoch=epoch)

    def run_once(name, plan):
        chaos.install(plan)
        root = tempfile.mkdtemp(prefix="bench-chaos-")
        cfg = KatibConfig()
        cfg.runtime.telemetry = False
        cfg.runtime.compile_service = False
        cfg.runtime.preemption_grace_seconds = 5.0
        c = ExperimentController(
            root_dir=root, devices=list(range(n_devices)), config=cfg
        )
        try:
            spec = ExperimentSpec(
                name=name,
                parameters=[
                    ParameterSpec(
                        "x", ParameterType.DOUBLE,
                        FeasibleSpace(min="0.1", max="1.0", step="0.0375"),
                    )
                ],
                objective=ObjectiveSpec(
                    type=ObjectiveType.MAXIMIZE, objective_metric_name="score"
                ),
                algorithm=AlgorithmSpec("grid"),
                trial_template=TrialTemplate(function=trial_fn),
                max_trial_count=n_trials,
                parallel_trial_count=n_devices,
            )
            c.create_experiment(spec)
            t0 = time.time()
            exp = c.run(name, timeout=300)
            wall = time.time() - t0
            assert exp.status.is_succeeded, exp.status.message
            rows_by_x = {}
            lost = 0
            for t in c.state.list_trials(name):
                x = t.assignments_dict()["x"]
                steps = [
                    int(float(r.value))
                    for r in c.obs_store.get_observation_log(
                        t.name, metric_name="epoch"
                    )
                ]
                if steps != list(range(1, epochs + 1)):
                    lost += 1  # gap, duplicate, or truncation = lost rows
                rows_by_x[x] = [
                    r.value
                    for r in c.obs_store.get_observation_log(
                        t.name, metric_name="score"
                    )
                ]
            preempted = {
                e.name
                for e in c.events.list(name)
                if e.reason == "TrialPreempted"
            }
            resumed_ok = all(
                t.condition.value == "Succeeded"
                for t in c.state.list_trials(name)
                if t.name in preempted
            )
            checkpointed = {
                e.name
                for e in c.events.list(name)
                if e.reason == "TrialPreempted"
                and "resumes from checkpoint" in e.message
            }
            plane_events = {
                r: sum(1 for e in c.events.list_all() if e.reason == r)
                for r in ("DeviceLost", "BackendFailedOver")
            }
            return {
                "wall_s": wall,
                "rows_by_x": rows_by_x,
                "lost": lost,
                "preempted": len(preempted),
                "checkpoint_resumed": len(checkpointed),
                "resumed_ok": resumed_ok,
                "plane_events": plane_events,
                "free_after": c.scheduler.allocator.free_count,
            }
        finally:
            c.close()
            chaos.install(None)

    # fault-free reference
    ref = run_once("chaos-ref", None)

    # chaos round: per-round backend acquisition through the device plane —
    # the wedged probe must cost one bounded attempt with a cached verdict,
    # never a lost round (ROADMAP "bench never loses a round")
    plan = chaos.parse_plan(
        "seed=5;wedge_probe=1;"
        + (f"revoke={max(n_trials // 4, 2)}@2;revoke={max(n_trials // 2, 3)}@3")
    )
    chaos.install(plan)
    backend_mod.reset_probe_state()
    probe_t0 = time.time()
    devices, probe_diag = deviceplane.acquire_backend(timeout_seconds=10.0)
    probe_s = time.time() - probe_t0
    backend_degraded = devices is None
    assert plan._wedges_left == 0, "the wedged probe was never exercised"
    assert probe_s < 10.0, f"wedged probe burned the whole timeout: {probe_s:.1f}s"

    faulty = run_once("chaos-faulty", plan)
    ratio = faulty["wall_s"] / max(ref["wall_s"], 1e-9)

    assert ref["lost"] == 0 and faulty["lost"] == 0, (ref["lost"], faulty["lost"])
    assert faulty["preempted"] >= 1, "no trial was preempted by the revocations"
    assert faulty["resumed_ok"], "a preempted trial did not resume to success"
    assert faulty["plane_events"]["DeviceLost"] >= 2, faulty["plane_events"]
    # checkpoint-resumed trials reproduce the fault-free rows bit-for-bit;
    # clean re-runs land on the same deterministic curve too
    assert faulty["rows_by_x"] == ref["rows_by_x"], "chaos run diverged"
    if not smoke:
        assert ratio <= 1.5, (
            f"chaos run took {faulty['wall_s']:.2f}s vs fault-free "
            f"{ref['wall_s']:.2f}s ({ratio:.2f}x > 1.5x)"
        )
    return {
        "trials": n_trials,
        "devices": n_devices,
        "injected_device_losses": 2,
        "injected_wedged_probes": 1,
        "probe_diag": probe_diag,
        "probe_seconds": round(probe_s, 3),
        "backend_degraded": backend_degraded,
        "fault_free_wall_s": round(ref["wall_s"], 3),
        "chaos_wall_s": round(faulty["wall_s"], 3),
        "wall_ratio": round(ratio, 3),
        "lost_observations": ref["lost"] + faulty["lost"],
        "trials_preempted": faulty["preempted"],
        "checkpoint_resumed": faulty["checkpoint_resumed"],
        "bit_identical": faulty["rows_by_x"] == ref["rows_by_x"],
        "device_lost_events": faulty["plane_events"]["DeviceLost"],
        "free_devices_after_chaos": faulty["free_after"],
        "target_ratio": 1.5,
        "within_target": ratio <= 1.5,
        "smoke": smoke,
    }


# Trial workload for the controller-kill harness: a subprocess trial that
# PUSHES one row per epoch straight into the observation db (durable against
# a controller SIGKILL) and checkpoints in the runtime/checkpoints.py pickle
# format AFTER each report — the report-then-save order the truncate-to-
# checkpoint recovery rule stitches back into one continuous execution.
_KILL_TRIAL_SCRIPT = """\
import os, pickle, sys, time

def latest_step():
    steps = []
    for fn in os.listdir("."):
        if fn.startswith("ckpt_") and fn.endswith(".pkl"):
            try:
                steps.append(int(fn[5:-4]))
            except ValueError:
                pass
    return max(steps) if steps else None

x = float(sys.argv[1])
epochs = int(sys.argv[2])
from katib_tpu.runtime.metrics import report_metrics  # env-bound db push

step = latest_step()
start = step + 1 if step is not None else 1
for epoch in range(start, epochs + 1):
    score = x * (1.0 - 0.8 ** epoch)
    time.sleep(0.05)
    report_metrics(score=score, epoch=epoch)
    tmp = "ckpt_%d.pkl.tmp" % epoch
    with open(tmp, "wb") as f:
        pickle.dump({"step": epoch, "state": {"epoch": epoch}}, f)
    os.replace(tmp, "ckpt_%d.pkl" % epoch)
"""

# Controller driver run as a SUBPROCESS so a SIGKILL injected by the chaos
# plan (kill_controller=N, fired from inside the recovery journal) kills a
# real controller process, orphaning its trial children — exactly the
# failure the lease + fencing + replay machinery exists for.
_KILL_DRIVER = """\
import json, os, sys, time

root, phase, n_trials, epochs, n_devices, parallel = (
    sys.argv[1], sys.argv[2], int(sys.argv[3]), int(sys.argv[4]),
    int(sys.argv[5]), int(sys.argv[6]),
)
from katib_tpu.api import (
    AlgorithmSpec, ExperimentSpec, FeasibleSpace, ObjectiveSpec,
    ObjectiveType, ParameterSpec, ParameterType, TrialParameterSpec,
    TrialTemplate,
)
from katib_tpu.api.spec import ResumePolicy
from katib_tpu.config import KatibConfig
from katib_tpu.controller.experiment import ExperimentController

cfg = KatibConfig()
cfg.runtime.telemetry = False
cfg.runtime.compile_service = False
cfg.runtime.tracing = False
c = ExperimentController(root_dir=root, devices=list(range(n_devices)), config=cfg)
name = "kill-sweep"
replay_s = 0.0
if phase == "create":
    step = 0.9 / max(n_trials - 1, 1)
    spec = ExperimentSpec(
        name=name,
        parameters=[ParameterSpec(
            "x", ParameterType.DOUBLE,
            FeasibleSpace(min="0.1", max="1.0", step=repr(step)),
        )],
        objective=ObjectiveSpec(
            type=ObjectiveType.MAXIMIZE, objective_metric_name="score"
        ),
        algorithm=AlgorithmSpec("grid"),
        trial_template=TrialTemplate(
            command=[sys.executable, os.path.join(root, "trial_script.py"),
                     "${trialParameters.x}", str(epochs)],
            trial_parameters=[TrialParameterSpec(name="x", reference="x")],
            env={"PYTHONPATH": os.environ.get("PYTHONPATH", "")},
        ),
        max_trial_count=n_trials,
        parallel_trial_count=parallel,
        resume_policy=ResumePolicy.FROM_VOLUME,
    )
    c.create_experiment(spec)
else:
    t0 = time.time()
    c.load_experiment(name)
    replay_s = time.time() - t0
    # emitted BEFORE run(): a chaos SIGKILL mid-run must not lose the
    # replay timing the harness asserts on
    print(json.dumps({"replay_seconds": replay_s}), flush=True)
exp = c.run(name, timeout=240)
print(json.dumps({
    "replay_seconds": replay_s,
    "succeeded": exp.status.is_succeeded,
    "recovered_events": sum(
        1 for e in c.events.list(name) if e.reason == "ControllerRecovered"
    ),
}))
c.close()
"""


def _bench_controller_kill_recovery(smoke: bool = False):
    """Crash-tolerant controller under injected SIGKILLs (ISSUE 14): the
    same checkpointed sweep runs fault-free (in-process reference) and then
    across controller subprocesses that the chaos plan hard-kills
    (``kill_controller=N``, fired deterministically from inside the
    recovery journal) at >= 2 journal points mid-flight. Each restart must
    take over the dead holder's lease immediately, fence orphaned trial
    processes, replay the journal, and truncate each observation log only
    to its last durable checkpoint. The finished sweep must show ZERO lost
    observations (every trial's epoch curve continuous 1..E, no gaps or
    duplicates), score rows bit-identical to the fault-free run, and every
    recovery replay bounded under 10s."""
    import shutil
    import signal as _signal
    import tempfile

    from katib_tpu.api import (
        AlgorithmSpec, ExperimentSpec, FeasibleSpace, ObjectiveSpec,
        ObjectiveType, ParameterSpec, ParameterType, TrialParameterSpec,
        TrialTemplate,
    )
    from katib_tpu.api.spec import ResumePolicy
    from katib_tpu.config import KatibConfig
    from katib_tpu.controller.experiment import ExperimentController
    from katib_tpu.db.state import ExperimentStateStore
    from katib_tpu.db.store import SqliteObservationStore

    n_trials = 4 if smoke else 10
    epochs = 4 if smoke else 6
    n_devices = parallel = 2 if smoke else 4
    # per-round journal-append kill points: early enough that every round
    # still has in-flight work when the SIGKILL lands (round 0: the first
    # terminals; later rounds: mid-recovery-dispatch of the requeued batch)
    kill_appends = [6, 5] if smoke else [8, 8, 6]
    repo = os.path.dirname(os.path.abspath(__file__))
    child_env_base = dict(os.environ)
    child_env_base["JAX_PLATFORMS"] = "cpu"
    child_env_base["PYTHONPATH"] = (
        repo + os.pathsep + child_env_base.get("PYTHONPATH", "")
    ).rstrip(os.pathsep)
    child_env_base.pop("KATIB_TPU_CHAOS", None)

    def rows_by_x(root):
        """(epoch rows, score rows) per x — read offline from the root."""
        state = ExperimentStateStore(os.path.join(root, "state"))
        state.load("kill-sweep")
        store = SqliteObservationStore(os.path.join(root, "observations.db"))
        epochs_by_x, scores_by_x, conditions = {}, {}, {}
        try:
            for t in state.list_trials("kill-sweep"):
                x = t.assignments_dict()["x"]
                epochs_by_x[x] = [
                    int(float(r.value))
                    for r in store.get_observation_log(t.name, metric_name="epoch")
                ]
                scores_by_x[x] = [
                    r.value
                    for r in store.get_observation_log(t.name, metric_name="score")
                ]
                conditions[x] = t.condition.value
        finally:
            store.close()
        return epochs_by_x, scores_by_x, conditions

    def run_child(root, phase, kill_at=None, timeout=300):
        env = dict(child_env_base)
        if kill_at is not None:
            env["KATIB_TPU_CHAOS"] = f"kill_controller={kill_at}"
        proc = subprocess.run(
            [sys.executable, "-c", _KILL_DRIVER, root, phase,
             str(n_trials), str(epochs), str(n_devices), str(parallel)],
            env=env, capture_output=True, text=True, timeout=timeout,
        )
        out = None
        replay = None
        for line in (proc.stdout or "").strip().splitlines():
            try:
                parsed = json.loads(line)
            except ValueError:
                continue
            out = parsed
            if "replay_seconds" in parsed and replay is None:
                replay = parsed["replay_seconds"]
        return proc.returncode, out, replay, proc.stderr

    # fault-free reference: same spec, driven in-process
    ref_root = tempfile.mkdtemp(prefix="bench-killref-")
    with open(os.path.join(ref_root, "trial_script.py"), "w") as f:
        f.write(_KILL_TRIAL_SCRIPT)
    cfg = KatibConfig()
    cfg.runtime.telemetry = False
    cfg.runtime.compile_service = False
    cfg.runtime.tracing = False
    ctrl = ExperimentController(
        root_dir=ref_root, devices=list(range(n_devices)), config=cfg
    )
    try:
        step = 0.9 / max(n_trials - 1, 1)
        spec = ExperimentSpec(
            name="kill-sweep",
            parameters=[ParameterSpec(
                "x", ParameterType.DOUBLE,
                FeasibleSpace(min="0.1", max="1.0", step=repr(step)),
            )],
            objective=ObjectiveSpec(
                type=ObjectiveType.MAXIMIZE, objective_metric_name="score"
            ),
            algorithm=AlgorithmSpec("grid"),
            trial_template=TrialTemplate(
                command=[sys.executable,
                         os.path.join(ref_root, "trial_script.py"),
                         "${trialParameters.x}", str(epochs)],
                trial_parameters=[TrialParameterSpec(name="x", reference="x")],
                env={"PYTHONPATH": child_env_base["PYTHONPATH"]},
            ),
            max_trial_count=n_trials,
            parallel_trial_count=parallel,
            resume_policy=ResumePolicy.FROM_VOLUME,
        )
        ctrl.create_experiment(spec)
        exp = ctrl.run("kill-sweep", timeout=240)
        assert exp.status.is_succeeded, exp.status.message
    finally:
        ctrl.close()
    ref_epochs, ref_scores, _ = rows_by_x(ref_root)
    assert all(
        steps == list(range(1, epochs + 1)) for steps in ref_epochs.values()
    ), "fault-free reference lost rows"

    # chaos rounds: each child controller is SIGKILLed at a journal point,
    # then a fresh child takes over the dead lease and recovers
    root = tempfile.mkdtemp(prefix="bench-kill-")
    with open(os.path.join(root, "trial_script.py"), "w") as f:
        f.write(_KILL_TRIAL_SCRIPT)
    kills = 0
    replays = []
    for i, kill_at in enumerate(kill_appends):
        phase = "create" if i == 0 else "resume"
        rcode, out, replay, err = run_child(root, phase, kill_at=kill_at)
        assert rcode == -_signal.SIGKILL, (
            f"round {i}: controller was not SIGKILLed (rc={rcode}); "
            f"raise kill_appends[{i}]\n{err[-2000:]}"
        )
        kills += 1
        if replay is not None:
            replays.append(replay)
    rcode, out, replay, err = run_child(root, "resume")
    assert rcode == 0 and out is not None and out["succeeded"], (
        f"final recovery run failed (rc={rcode}): {err[-2000:]}"
    )
    replays.append(replay)
    recovered_events = out["recovered_events"]

    chaos_epochs, chaos_scores, conditions = rows_by_x(root)
    lost = {
        x: steps
        for x, steps in chaos_epochs.items()
        if steps != list(range(1, epochs + 1))
    }
    assert not lost, f"lost/duplicated observations after recovery: {lost}"
    assert chaos_scores == ref_scores, (
        "recovered sweep rows are not bit-identical to the fault-free run"
    )
    assert set(conditions.values()) == {"Succeeded"}, conditions
    assert kills >= 2, kills
    assert recovered_events >= 1, "final load did not record ControllerRecovered"
    max_replay = max(replays) if replays else 0.0
    assert max_replay < 10.0, f"recovery replay took {max_replay:.1f}s (>= 10s)"
    shutil.rmtree(ref_root, ignore_errors=True)
    shutil.rmtree(root, ignore_errors=True)
    return {
        "trials": n_trials,
        "epochs": epochs,
        "devices": n_devices,
        "sigkills_injected": kills,
        "kill_journal_appends": kill_appends,
        "lost_observations": len(lost),
        "bit_identical": chaos_scores == ref_scores,
        "recovery_replays": len(replays),
        "max_replay_seconds": round(max_replay, 3),
        "replay_bound_seconds": 10.0,
        "smoke": smoke,
    }


# In-process entry-point trial for the control-plane load harness: cheap,
# deterministic (score depends only on x and epoch), and device-slot-bound
# (the per-epoch dwell stands in for accelerator time on the 1-core CPU
# box), so aggregate completed-trials/sec is governed by how many device
# slots the control plane can keep busy — which is exactly what sharding
# multiplies.
_CP_TRIAL_MODULE = """\
import time

EPOCHS = {epochs}
DWELL = {dwell}

def run_trial(assignments, ctx):
    x = float(assignments["x"])
    for epoch in range(1, EPOCHS + 1):
        time.sleep(DWELL)
        ctx.report(score=x * (1.0 - 0.8 ** epoch), epoch=epoch)
"""


def _bench_control_plane_scaling(smoke: bool = False):
    """Sharded control plane under a standing load harness (ISSUE 15): the
    same batch of cheap experiments is driven through REAL replica
    subprocesses over the HTTP/JSON wire protocol — specs routed by the
    client-side placement router, status polled from the owners — at 1 vs
    N replicas sharing one state root (WAL SQLite, per-experiment placement
    leases). Aggregate completed-trials/sec must scale >= 2.5x at 3
    replicas (each replica supervises its own device pool; trials are
    device-slot-bound). A third phase SIGKILLs one replica mid-run: the
    survivors must fail its experiments over inside the placement-lease
    TTL, finish the batch with ZERO lost observations (every epoch curve
    continuous 1..E) and score rows bit-identical to the fault-free run.

    Scale knobs (the harness is the standing tool for finding the next
    control-plane bottleneck): BENCH_CP_EXPERIMENTS / BENCH_CP_TRIALS /
    BENCH_CP_EPOCHS / BENCH_CP_DWELL / BENCH_CP_REPLICAS. Ambient
    KATIB_TPU_* env passes through to the replica subprocesses, so
    `KATIB_TPU_INGEST_FRAMED=1 python bench.py control_plane_scaling` runs
    every phase — the SIGKILL failover included — on the framed ingest
    plane (ISSUE 16); the thousands-of-experiments streaming regime has
    its own dedicated scenario, `ingest_throughput`."""
    import shutil
    import signal as _signal
    import tempfile

    from katib_tpu.client.katib_client import ReplicaRouter
    from katib_tpu.db.state import ExperimentStateStore
    from katib_tpu.db.store import SqliteObservationStore
    from katib_tpu.tracing import wire_tracing_from_env

    # distributed tracing plane (ISSUE 19): with KATIB_TPU_WIRE_TRACING=1
    # (+ KATIB_TPU_TRACING=1) in the ambient env, every phase runs traced —
    # the harness then also scrapes the fleet's /metrics and asserts the
    # per-tenant SLO series and cross-replica merged traces below
    wire_tracing = wire_tracing_from_env()

    # full-mode shape: every experiment dispatches as ONE round (trials ==
    # parallel), so experiment wall == trial wall and the throughput ratio
    # measures the control plane, not reconcile round-trip quantization;
    # measured 2.86x at 3 replicas on the 1-core CPU box with these sizes
    n_exps = int(os.environ.get("BENCH_CP_EXPERIMENTS", "4" if smoke else "18"))
    n_trials = int(os.environ.get("BENCH_CP_TRIALS", "3" if smoke else "4"))
    epochs = int(os.environ.get("BENCH_CP_EPOCHS", "2" if smoke else "4"))
    dwell = float(os.environ.get("BENCH_CP_DWELL", "0.15" if smoke else "0.45"))
    n_replicas = int(os.environ.get("BENCH_CP_REPLICAS", "2" if smoke else "3"))
    devices_per_replica = 4 if smoke else 8
    parallel = 2 if smoke else 4
    lease_ttl = 8.0
    repo = os.path.dirname(os.path.abspath(__file__))

    def exp_names():
        return [f"cp-{i:03d}" for i in range(n_exps)]

    def spec_for(name):
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
                "entryPoint": "cp_trial:run_trial",
                "trialParameters": [{"name": "x", "reference": "x"}],
            },
            "maxTrialCount": n_trials,
            "parallelTrialCount": parallel,
            "resumePolicy": "FromVolume",
        }

    def is_done(status_doc):
        if not status_doc:
            return False
        return any(
            c.get("type") in ("Succeeded", "Failed") and c.get("status")
            for c in status_doc.get("status", {}).get("conditions", [])
        )

    def rows_by_key(root, names):
        """{(experiment, x): (epoch ints, score strings)} read offline."""
        state = ExperimentStateStore(os.path.join(root, "state"))
        store = SqliteObservationStore(os.path.join(root, "observations.db"))
        epochs_by, scores_by = {}, {}
        try:
            for name in names:
                state.load(name)
                for t in state.list_trials(name):
                    key = (name, t.assignments_dict()["x"])
                    epochs_by[key] = [
                        int(float(r.value))
                        for r in store.get_observation_log(t.name, metric_name="epoch")
                    ]
                    scores_by[key] = [
                        r.value
                        for r in store.get_observation_log(t.name, metric_name="score")
                    ]
        finally:
            store.close()
        return epochs_by, scores_by

    def run_phase(replicas, kill=False, phase_timeout=420.0):
        root = tempfile.mkdtemp(prefix="bench-cp-")
        # the kill phase slows each epoch down so the SIGKILL is guaranteed
        # to land on in-flight work; scores depend only on (x, epoch), so
        # the bit-identity comparison against the fault-free phase holds
        phase_dwell = max(dwell, 0.4) if kill else dwell
        with open(os.path.join(root, "cp_trial.py"), "w") as f:
            f.write(_CP_TRIAL_MODULE.format(epochs=epochs, dwell=phase_dwell))
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": (
                repo + os.pathsep + root + os.pathsep + env.get("PYTHONPATH", "")
            ).rstrip(os.pathsep),
            "KATIB_TPU_REPLICAS": str(replicas),
            "KATIB_TPU_REPLICA_CAPACITY": str(n_exps + 4),
            "KATIB_TPU_PLACEMENT_LEASE_SECONDS": str(lease_ttl),
            # replicas run lean: no telemetry/tracing/compile service, and
            # DIRECT per-report SQLite commits (obslog_buffered=0) so every
            # acknowledged row is durable when the SIGKILL lands. Tracing is
            # a pass-through default (not a pin) so the distributed-trace
            # smoke (scripts/check.sh, ISSUE 19) can arm
            # KATIB_TPU_TRACING=1 KATIB_TPU_WIRE_TRACING=1 across the fleet
            "KATIB_TPU_TELEMETRY": "0",
            "KATIB_TPU_COMPILE_SERVICE": "0",
            "KATIB_TPU_TRACING": os.environ.get("KATIB_TPU_TRACING", "0"),
            "KATIB_TPU_OBSLOG_BUFFERED": "0",
        })
        env.pop("KATIB_TPU_CHAOS", None)
        procs = {}
        logs = []
        deadline = time.time() + phase_timeout
        try:
            for i in range(replicas):
                rid = f"r{i}"
                out = open(os.path.join(root, f"{rid}.log"), "w+")
                logs.append(out)
                procs[rid] = subprocess.Popen(
                    [sys.executable, "-m", "katib_tpu.controller.replica",
                     "--root", root, "--replica-id", rid,
                     "--devices", str(devices_per_replica)],
                    env=env, stdout=out, stderr=out, text=True,
                )
            router = ReplicaRouter(root)
            while len(router.live_replicas()) < replicas:
                if time.time() > deadline:
                    raise TimeoutError(
                        f"replicas never registered; see {root}/r*.log"
                    )
                time.sleep(0.2)
            # warmup: one 1-trial experiment per replica so the first-trial
            # costs (module import, jax-backed compile-cache init) are paid
            # before the measured window
            warmups = []
            for i in range(replicas):
                wname = f"cp-warm-{i}"
                w = dict(spec_for(wname))
                w["maxTrialCount"] = 1
                w["parallelTrialCount"] = 1
                router.create_experiment(w)
                warmups.append(wname)
            while not all(is_done(router.experiment_status(w)) for w in warmups):
                if time.time() > deadline:
                    raise TimeoutError("warmup experiments never completed")
                time.sleep(0.3)

            names = exp_names()
            t0 = time.time()
            for name in names:
                router.create_experiment(spec_for(name))
            pending = set(names)
            kill_time = None
            victim = None
            victim_claims = set()
            failover_seen = {}  # experiment -> seconds after the kill the
            # placement table first showed a SURVIVOR owning it
            while pending:
                if time.time() > deadline:
                    raise TimeoutError(
                        f"{len(pending)} experiment(s) never completed: "
                        f"{sorted(pending)[:4]}; see {root}/r*.log"
                    )
                for name in list(pending):
                    if is_done(router.experiment_status(name)):
                        pending.discard(name)
                if kill and kill_time is None and time.time() - t0 > 0.6:
                    # mid-run SIGKILL: the replica holding the most still-
                    # pending placements dies without warning, while its
                    # trials are in flight (the trigger fires on the first
                    # poll after trials have had time to start)
                    counts = {}
                    rows = router.table()["leases"]
                    for row in rows:
                        if (
                            row.get("state") == "active"
                            and row.get("replica") in procs
                            and row.get("experiment") in pending
                        ):
                            counts[row["replica"]] = counts.get(row["replica"], 0) + 1
                    if counts:
                        victim = max(counts, key=counts.get)
                        victim_claims = {
                            row["experiment"]
                            for row in rows
                            if row.get("replica") == victim
                            and row.get("state") == "active"
                            and row.get("experiment") in pending
                        }
                        procs[victim].send_signal(_signal.SIGKILL)
                        procs[victim].wait()  # reap: a dead pid, not a zombie
                        kill_time = time.time()
                if kill_time is not None:
                    for row in router.table()["leases"]:
                        name = row.get("experiment", "")
                        if (
                            name in victim_claims
                            and name not in failover_seen
                            and row.get("replica") != victim
                        ):
                            failover_seen[name] = time.time() - kill_time
                time.sleep(0.25)
            wall = time.time() - t0
            metrics_text = ""
            if wire_tracing:
                import urllib.request

                for rep in router.table()["replicas"]:
                    if not rep.get("alive") or not rep.get("url"):
                        continue
                    try:
                        with urllib.request.urlopen(
                            rep["url"].rstrip("/") + "/metrics", timeout=10
                        ) as resp:
                            metrics_text += resp.read().decode("utf-8", "replace")
                    except OSError:
                        pass
            total_trials = n_exps * n_trials
            failovers = 0
            if kill:
                assert kill_time is not None, "kill trigger never fired"
                for rid in procs:
                    if rid == victim:
                        continue
                    url = next(
                        (
                            r["url"] for r in router.table()["replicas"]
                            if r.get("replica") == rid
                        ),
                        None,
                    )
                    status = router._client(url).replica_status() if url else None
                    if status:
                        failovers += int(status.get("failovers", 0))
            epochs_by, scores_by = rows_by_key(root, names)
            return {
                "root": root,
                "wall": wall,
                "trials_per_sec": total_trials / wall,
                "epochs_by": epochs_by,
                "scores_by": scores_by,
                "kill_time": kill_time,
                "victim": victim,
                "victim_claims": sorted(victim_claims),
                "failover_seconds": sorted(failover_seen.values()),
                "failovers": failovers,
                "metrics_text": metrics_text,
            }
        finally:
            for proc in procs.values():
                if proc.poll() is None:
                    proc.terminate()
            for proc in procs.values():
                if proc.poll() is None:
                    try:
                        proc.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        proc.kill()
            for out in logs:
                out.close()

    # phase A: single replica — the fault-free reference AND the scaling
    # baseline
    ref = run_phase(1)
    lost_ref = {
        k: v for k, v in ref["epochs_by"].items()
        if v != list(range(1, epochs + 1))
    }
    assert not lost_ref, f"single-replica reference lost rows: {lost_ref}"

    # phase B: N replicas, no faults — the throughput claim
    scaled = run_phase(n_replicas)
    speedup = scaled["trials_per_sec"] / ref["trials_per_sec"]
    if not smoke:
        assert speedup >= 2.5, (
            f"aggregate throughput scaled only {speedup:.2f}x at "
            f"{n_replicas} replicas (>= 2.5x required): "
            f"{ref['trials_per_sec']:.2f} -> {scaled['trials_per_sec']:.2f} trials/s"
        )

    # phase C: N replicas + mid-run SIGKILL — the failover claim
    chaos = run_phase(n_replicas, kill=True)
    lost = {
        k: v for k, v in chaos["epochs_by"].items()
        if v != list(range(1, epochs + 1))
    }
    assert not lost, f"lost/duplicated observations after failover: {lost}"
    assert chaos["scores_by"] == ref["scores_by"], (
        "failed-over sweep rows are not bit-identical to the fault-free run"
    )
    assert chaos["failovers"] >= 1, (
        f"no survivor recorded a failover (victim {chaos['victim']} held "
        f"{chaos['victim_claims']})"
    )
    max_failover = max(chaos["failover_seconds"], default=0.0)
    assert max_failover < lease_ttl, (
        f"failover took {max_failover:.1f}s (>= placement lease ttl {lease_ttl}s)"
    )

    # distributed-trace smoke assertions (ISSUE 19): only when the ambient
    # env armed wire tracing — the knob-off run stays byte-for-byte PR 17
    cross_replica_traces = 0
    if wire_tracing:
        from katib_tpu.tracing import experiment_traces

        assert (
            "katib_rpc_latency_seconds" in scaled["metrics_text"]
            and 'tenant="' in scaled["metrics_text"]
        ), "wire tracing on but no per-tenant rpc latency series on /metrics"
        if os.environ.get("KATIB_TPU_SLO_OBJECTIVES"):
            assert "katib_slo_violations_total" in scaled["metrics_text"], (
                "SLO objectives configured but no violation counter on /metrics"
            )
        for name in exp_names():
            traces = experiment_traces(chaos["root"], name)
            assert traces, (
                f"no merged trace for experiment {name} with wire tracing on"
            )
        for name in chaos["victim_claims"]:
            for t in experiment_traces(chaos["root"], name):
                reps = set(t.get("replicas") or [])
                if chaos["victim"] in reps and any(
                    r != chaos["victim"] for r in reps
                ):
                    cross_replica_traces += 1
                    break
        if chaos["victim_claims"]:
            assert cross_replica_traces >= 1, (
                f"victim {chaos['victim']} held {chaos['victim_claims']} but "
                "no experiment's merged trace covers both the victim and a "
                "survivor replica"
            )
    for phase in (ref, scaled, chaos):
        shutil.rmtree(phase["root"], ignore_errors=True)
    return {
        "experiments": n_exps,
        "trials_per_experiment": n_trials,
        "epochs": epochs,
        "devices_per_replica": devices_per_replica,
        "replicas": n_replicas,
        "trials_per_sec_1_replica": round(ref["trials_per_sec"], 3),
        f"trials_per_sec_{n_replicas}_replicas": round(scaled["trials_per_sec"], 3),
        "speedup": round(speedup, 3),
        "speedup_target": 2.5 if not smoke else None,
        "sigkill_victim": chaos["victim"],
        "victim_experiments": len(chaos["victim_claims"]),
        "failovers": chaos["failovers"],
        "max_failover_seconds": round(max_failover, 3),
        "failover_bound_seconds": lease_ttl,
        "lost_observations": len(lost),
        "bit_identical": chaos["scores_by"] == ref["scores_by"],
        "wire_tracing": wire_tracing,
        "cross_replica_traces": cross_replica_traces,
        "smoke": smoke,
    }


def _bench_multi_tenant_scaling(smoke: bool = False):
    """Multi-tenant service tier under load (ISSUE 17): N tenants drive the
    same aggregate workload through REAL replica subprocesses with the
    tenancy plane armed — per-tenant scoped tokens, namespaced experiments,
    replica-shared admission buckets. Three phases:

    A. tenancy OFF, same replicas/workload — the PR 16 throughput baseline;
    B. tenancy ON, one router per tenant — aggregate trials/sec must hold
       >= 0.9x the baseline (isolation is not allowed to cost the plane),
       then a fairness probe hammers per-tenant admissions (no tenant may
       exceed its admission share by >10%; the starved low-quota tenant
       still progresses) and an adversarial probe fires every cross-tenant
       verb expecting 403s — zero leaks;
    C. tenancy ON + mid-run replica SIGKILL — failover with ZERO lost
       observations (every epoch curve continuous) and score rows
       bit-identical to phase B.

    Scale knobs: BENCH_MT_TENANTS / BENCH_MT_EXPERIMENTS (per tenant) /
    BENCH_MT_TRIALS / BENCH_MT_EPOCHS / BENCH_MT_DWELL / BENCH_MT_REPLICAS.
    Ambient KATIB_TPU_* env passes through, so the framed ingest plane can
    be armed underneath (`KATIB_TPU_INGEST_FRAMED=1`)."""
    import shutil
    import signal as _signal
    import tempfile

    from katib_tpu.client.katib_client import ReplicaRouter
    from katib_tpu.db.state import ExperimentStateStore
    from katib_tpu.db.store import SqliteObservationStore
    from katib_tpu.service.httpapi import HttpApiClient, RpcError
    from katib_tpu.service.tenancy import SCOPE_ADMIN, TenantRegistry

    n_tenants = int(os.environ.get("BENCH_MT_TENANTS", "4" if smoke else "8"))
    exps_per_tenant = int(os.environ.get("BENCH_MT_EXPERIMENTS", "1" if smoke else "2"))
    n_trials = int(os.environ.get("BENCH_MT_TRIALS", "2" if smoke else "3"))
    epochs = int(os.environ.get("BENCH_MT_EPOCHS", "2" if smoke else "3"))
    dwell = float(os.environ.get("BENCH_MT_DWELL", "0.15" if smoke else "0.35"))
    n_replicas = int(os.environ.get("BENCH_MT_REPLICAS", "2" if smoke else "3"))
    devices_per_replica = 4 if smoke else 8
    parallel = 2
    lease_ttl = 8.0
    probe_attempts = 6 if smoke else 10
    root_token = "bench-root-token"
    tenants = [f"ten{i}" for i in range(n_tenants)]
    starved = tenants[0]
    # the starved tenant's bucket barely covers its main workload (burst
    # max(1, Q/6)); everyone else is effectively unlimited for the run
    quotas = {t: (12.0 if t == starved else 600.0) for t in tenants}
    n_exps_total = n_tenants * exps_per_tenant
    repo = os.path.dirname(os.path.abspath(__file__))

    def spec_for(name):
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
                "entryPoint": "cp_trial:run_trial",
                "trialParameters": [{"name": "x", "reference": "x"}],
            },
            "maxTrialCount": n_trials,
            "parallelTrialCount": parallel,
            "resumePolicy": "FromVolume",
        }

    def is_done(status_doc):
        if not status_doc:
            return False
        return any(
            c.get("type") in ("Succeeded", "Failed") and c.get("status")
            for c in status_doc.get("status", {}).get("conditions", [])
        )

    def rows_by_key(root, names):
        state = ExperimentStateStore(os.path.join(root, "state"))
        store = SqliteObservationStore(os.path.join(root, "observations.db"))
        epochs_by, scores_by = {}, {}
        try:
            for name in names:
                state.load(name)
                for t in state.list_trials(name):
                    key = (name, t.assignments_dict()["x"])
                    epochs_by[key] = [
                        int(float(r.value))
                        for r in store.get_observation_log(t.name, metric_name="epoch")
                    ]
                    scores_by[key] = [
                        r.value
                        for r in store.get_observation_log(t.name, metric_name="score")
                    ]
        finally:
            store.close()
        return epochs_by, scores_by

    def run_phase(tenancy, kill=False, probe=False, phase_timeout=420.0):
        root = tempfile.mkdtemp(prefix="bench-mt-")
        phase_dwell = max(dwell, 0.4) if kill else dwell
        with open(os.path.join(root, "cp_trial.py"), "w") as f:
            f.write(_CP_TRIAL_MODULE.format(epochs=epochs, dwell=phase_dwell))
        tokens = {}
        if tenancy:
            reg = TenantRegistry(root)
            for t in tenants:
                rec = reg.create(t, admission_per_minute=quotas[t])
                tokens[t] = rec.tokens[SCOPE_ADMIN]
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": (
                repo + os.pathsep + root + os.pathsep + env.get("PYTHONPATH", "")
            ).rstrip(os.pathsep),
            "KATIB_TPU_REPLICAS": str(n_replicas),
            "KATIB_TPU_REPLICA_CAPACITY": str(
                n_exps_total + n_tenants * probe_attempts + 8
            ),
            "KATIB_TPU_PLACEMENT_LEASE_SECONDS": str(lease_ttl),
            "KATIB_TPU_TENANCY": "1" if tenancy else "0",
            "KATIB_TPU_TELEMETRY": "0",
            "KATIB_TPU_COMPILE_SERVICE": "0",
            "KATIB_TPU_TRACING": "0",
            "KATIB_TPU_OBSLOG_BUFFERED": "0",
        })
        env.pop("KATIB_TPU_CHAOS", None)
        procs = {}
        logs = []
        deadline = time.time() + phase_timeout
        try:
            for i in range(n_replicas):
                rid = f"r{i}"
                out = open(os.path.join(root, f"{rid}.log"), "w+")
                logs.append(out)
                cmd = [sys.executable, "-m", "katib_tpu.controller.replica",
                       "--root", root, "--replica-id", rid,
                       "--devices", str(devices_per_replica)]
                if tenancy:
                    # the global token stays the break-glass admin: trial
                    # subprocesses inherit it and write via the open path
                    cmd += ["--token", root_token]
                procs[rid] = subprocess.Popen(
                    cmd, env=env, stdout=out, stderr=out, text=True
                )
            t_start = time.time()
            admin_router = ReplicaRouter(
                root, token=root_token if tenancy else None
            )
            while len(admin_router.live_replicas()) < n_replicas:
                if time.time() > deadline:
                    raise TimeoutError(
                        f"replicas never registered; see {root}/r*.log"
                    )
                time.sleep(0.2)
            routers = {
                t: ReplicaRouter(root, token=tokens[t]) for t in tenants
            } if tenancy else {}
            # warmup: pay first-trial import/compile costs off the clock
            warmups = []
            for i in range(n_replicas):
                wname = f"warm{i}"
                w = dict(spec_for(wname))
                w["maxTrialCount"] = 1
                w["parallelTrialCount"] = 1
                created = admin_router.create_experiment(w)
                warmups.append(created.get("created", wname))
            while not all(
                is_done(admin_router.experiment_status(w)) for w in warmups
            ):
                if time.time() > deadline:
                    raise TimeoutError("warmup experiments never completed")
                time.sleep(0.3)

            # the measured window: every tenant submits its batch (bare
            # names — the wire namespaces them under the caller's tenant)
            created_names = {}  # tenant -> [namespaced names]
            t0 = time.time()
            if tenancy:
                for t in tenants:
                    created_names[t] = []
                    for i in range(exps_per_tenant):
                        got = routers[t].create_experiment(spec_for(f"mt{i}"))
                        created_names[t].append(got["created"])
            else:
                created_names[""] = []
                for i in range(n_exps_total):
                    got = admin_router.create_experiment(spec_for(f"mt{i:03d}"))
                    created_names[""].append(got.get("created", f"mt{i:03d}"))
            names = [n for ns in created_names.values() for n in ns]
            pending = set(names)
            kill_time, victim, victim_claims = None, None, set()
            while pending:
                if time.time() > deadline:
                    raise TimeoutError(
                        f"{len(pending)} experiment(s) never completed: "
                        f"{sorted(pending)[:4]}; see {root}/r*.log"
                    )
                for name in list(pending):
                    if is_done(admin_router.experiment_status(name)):
                        pending.discard(name)
                if kill and kill_time is None and time.time() - t0 > 0.6:
                    counts = {}
                    rows = admin_router.table()["leases"]
                    for row in rows:
                        if (
                            row.get("state") == "active"
                            and row.get("replica") in procs
                            and row.get("experiment") in pending
                        ):
                            counts[row["replica"]] = counts.get(row["replica"], 0) + 1
                    if counts:
                        victim = max(counts, key=counts.get)
                        victim_claims = {
                            row["experiment"] for row in rows
                            if row.get("replica") == victim
                            and row.get("state") == "active"
                            and row.get("experiment") in pending
                        }
                        procs[victim].send_signal(_signal.SIGKILL)
                        procs[victim].wait()
                        kill_time = time.time()
                time.sleep(0.25)
            wall = time.time() - t0
            if kill:
                assert kill_time is not None, "kill trigger never fired"

            grants, leaks = {}, []
            if probe and tenancy:
                # fairness probe: every tenant hammers more creates than its
                # bucket can hold; grants are bounded by the quota share
                for t in tenants:
                    grants[t] = 0
                    for j in range(probe_attempts):
                        p = dict(spec_for(f"pr{j}"))
                        p["maxTrialCount"] = 1
                        p["parallelTrialCount"] = 1
                        try:
                            routers[t].create_experiment(p)
                            grants[t] += 1
                        except (RpcError, RuntimeError):
                            pass
                probe_elapsed = time.time() - t_start
                for t in tenants:
                    burst = max(1.0, quotas[t] / 6.0)
                    share = burst + quotas[t] * probe_elapsed / 60.0
                    # main-workload creates already drew from the bucket, so
                    # this bound is conservative; >10% over it is a leak
                    assert grants[t] + exps_per_tenant <= 1.1 * share + 1, (
                        f"tenant {t} exceeded its admission share: "
                        f"{grants[t]} probe grants + {exps_per_tenant} creates "
                        f"vs share {share:.1f} over {probe_elapsed:.0f}s"
                    )
                assert grants[starved] < probe_attempts, (
                    f"starved tenant {starved} was never refused "
                    f"({grants[starved]}/{probe_attempts} probes admitted)"
                )
                # adversarial probe: tenant[1]'s token against tenant[2]'s
                # namespace on EVERY replica — each non-403 is a leak
                attacker, target = tenants[1], tenants[2]
                target_exp = created_names[target][0]
                row = {"timestamp": 1.0, "metricName": "score", "value": "1"}
                rpc_probes = [
                    ("GetObservationLog", {"trialName": f"{target_exp}-t0"}),
                    ("ReportObservationLog",
                     {"trialName": f"{target_exp}-t0", "metricLogs": [row]}),
                    ("TruncateObservationLog",
                     {"trialName": f"{target_exp}-t0", "afterTime": 0.0}),
                    ("DeleteObservationLog", {"trialName": f"{target_exp}-t0"}),
                    ("GetSuggestions",
                     {"experiment": {"name": target_exp},
                      "currentRequestNumber": 1}),
                ]
                for rep in admin_router.live_replicas():
                    cli = HttpApiClient(
                        rep["url"], token=tokens[attacker], retries=1
                    )
                    for method, payload in rpc_probes:
                        try:
                            cli.call(method, payload)
                            leaks.append(f"{rep['replica']}:{method}")
                        except RpcError as e:
                            if e.code != 403:
                                leaks.append(
                                    f"{rep['replica']}:{method}:HTTP{e.code}"
                                )
                    try:
                        if cli.experiment_status(target_exp) is not None:
                            leaks.append(f"{rep['replica']}:experiment_status")
                    except RpcError as e:
                        if e.code != 403:
                            leaks.append(
                                f"{rep['replica']}:experiment_status:HTTP{e.code}"
                            )
                    status = cli.replica_status()
                    foreign = [
                        n for n in (status or {}).get("claimed", [])
                        if not n.startswith(f"{attacker}--")
                    ]
                    if foreign:
                        leaks.append(f"{rep['replica']}:claimed:{foreign}")
                assert not leaks, f"cross-tenant probe leaked: {leaks}"

            epochs_by, scores_by = rows_by_key(root, names)
            return {
                "root": root,
                "wall": wall,
                "trials_per_sec": (n_exps_total * n_trials) / wall,
                "epochs_by": epochs_by,
                "scores_by": scores_by,
                "victim": victim,
                "victim_claims": sorted(victim_claims),
                "grants": grants,
                "leaks": leaks,
            }
        finally:
            for proc in procs.values():
                if proc.poll() is None:
                    proc.terminate()
            for proc in procs.values():
                if proc.poll() is None:
                    try:
                        proc.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        proc.kill()
            for out in logs:
                out.close()

    timeout_s = 300.0 if smoke else 480.0
    # phase A: tenancy OFF — the PR 16 baseline this plane must not tax
    base = run_phase(tenancy=False, phase_timeout=timeout_s)
    # phase B: the tenant fleet + fairness/adversarial probes
    tenant = run_phase(tenancy=True, probe=True, phase_timeout=timeout_s)
    ratio = tenant["trials_per_sec"] / base["trials_per_sec"]
    if not smoke:
        assert ratio >= 0.9, (
            f"tenancy plane costs too much: {ratio:.2f}x of the baseline "
            f"({base['trials_per_sec']:.2f} -> {tenant['trials_per_sec']:.2f} "
            "trials/s; >= 0.9x required)"
        )
    starved_trials = sum(
        1 for (name, _x) in tenant["epochs_by"] if name.startswith(f"{starved}--")
    )
    assert starved_trials > 0, f"starved tenant {starved} made no progress"

    # phase C: the tenant fleet through a mid-run replica SIGKILL
    chaos = run_phase(tenancy=True, kill=True, phase_timeout=timeout_s)
    lost = {
        k: v for k, v in chaos["epochs_by"].items()
        if v != list(range(1, epochs + 1))
    }
    assert not lost, f"lost/duplicated observations after failover: {lost}"
    assert chaos["scores_by"] == tenant["scores_by"], (
        "failed-over tenant rows are not bit-identical to the fault-free run"
    )
    for phase in (base, tenant, chaos):
        shutil.rmtree(phase["root"], ignore_errors=True)
    return {
        "tenants": n_tenants,
        "experiments_per_tenant": exps_per_tenant,
        "trials_per_experiment": n_trials,
        "epochs": epochs,
        "replicas": n_replicas,
        "trials_per_sec_baseline": round(base["trials_per_sec"], 3),
        "trials_per_sec_tenancy": round(tenant["trials_per_sec"], 3),
        "throughput_ratio": round(ratio, 3),
        "throughput_floor": 0.9 if not smoke else None,
        "starved_tenant": starved,
        "starved_tenant_trials": starved_trials,
        "probe_grants": tenant["grants"],
        "cross_tenant_leaks": len(tenant["leaks"]),
        "sigkill_victim": chaos["victim"],
        "victim_experiments": len(chaos["victim_claims"]),
        "lost_observations": len(lost),
        "bit_identical": chaos["scores_by"] == tenant["scores_by"],
        "smoke": smoke,
    }


def _bench_ingest_throughput(smoke: bool = False):
    """The thousands-of-concurrent-experiments ingest regime (ISSUE 16):
    thousands of experiments' streaming trials push observation rows at
    REAL replica subprocesses sharing one WAL SQLite root, once over the
    PR 15 HTTP/JSON wire (`ReportObservationLog` per report) and once over
    the framed ingest plane (service/ingest.py: persistent sockets,
    struct-packed frames, server-side coalescing into one group commit).
    Aggregate observation-rows/sec must be >= 5x with framed ingest on at
    3 replicas (full mode). A final framed phase SIGKILLs one replica
    mid-stream: streamers reroute to the survivors and resend their
    unacked batches; the per-entry idempotent duplicate drop must land the
    full row set exactly once — zero lost observations, every row
    bit-identical to the deterministic expectation (timestamps compared as
    raw IEEE-754 doubles, the truncate-to-checkpoint contract).

    Scale knobs: BENCH_ING_EXPERIMENTS / BENCH_ING_TRIALS /
    BENCH_ING_REPORTS / BENCH_ING_STREAMERS / BENCH_ING_REPLICAS."""
    import shutil
    import signal as _signal
    import tempfile
    import threading

    from katib_tpu.client.katib_client import ReplicaRouter
    from katib_tpu.db.store import MetricLog, SqliteObservationStore
    from katib_tpu.service.httpapi import HttpRemoteObservationStore, RpcError
    from katib_tpu.service.ingest import FramedObservationStore

    n_exps = int(os.environ.get("BENCH_ING_EXPERIMENTS", "30" if smoke else "2000"))
    n_trials = int(os.environ.get("BENCH_ING_TRIALS", "1"))
    n_reports = int(os.environ.get("BENCH_ING_REPORTS", "2" if smoke else "3"))
    n_streamers = int(os.environ.get("BENCH_ING_STREAMERS", "6" if smoke else "24"))
    n_replicas = int(os.environ.get("BENCH_ING_REPLICAS", "2" if smoke else "3"))
    repo = os.path.dirname(os.path.abspath(__file__))
    base_ts = 1_700_000_000.0  # deterministic: rows must be bit-identical

    def trial_names():
        return [
            f"ing-{e:04d}-t{t}" for e in range(n_exps) for t in range(n_trials)
        ]

    def expected_rows(trial):
        """The exact (timestamp, metric_name, value) triples this trial
        reports — what must be in the store afterwards, nothing else."""
        idx = int(trial[4:8]) * n_trials + int(trial.rsplit("t", 1)[1])
        x = 0.1 + (idx % 97) * 0.009
        rows = []
        for step in range(1, n_reports + 1):
            ts = base_ts + idx * 1e-3 + step * 1e-6
            rows.append((ts, "epoch", str(float(step))))
            rows.append((ts, "score", str(x * (1 - 0.8 ** step))))
        return rows

    def spawn_replicas(root, framed):
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": (repo + os.pathsep + env.get("PYTHONPATH", "")).rstrip(os.pathsep),
            "KATIB_TPU_REPLICAS": str(n_replicas),
            "KATIB_TPU_INGEST_FRAMED": "1" if framed else "0",
            # direct per-batch SQLite commits: every acked row is durable
            # when the SIGKILL lands (the failover phase's contract)
            "KATIB_TPU_TELEMETRY": "0",
            "KATIB_TPU_COMPILE_SERVICE": "0",
            "KATIB_TPU_TRACING": "0",
            "KATIB_TPU_OBSLOG_BUFFERED": "0",
        })
        env.pop("KATIB_TPU_CHAOS", None)
        procs, logs = {}, []
        for i in range(n_replicas):
            rid = f"r{i}"
            out = open(os.path.join(root, f"{rid}.log"), "w+")
            logs.append(out)
            procs[rid] = subprocess.Popen(
                [sys.executable, "-m", "katib_tpu.controller.replica",
                 "--root", root, "--replica-id", rid, "--devices", "2"],
                env=env, stdout=out, stderr=out, text=True,
            )
        return procs, logs

    def endpoints(router, framed, deadline):
        """[(rpc_url, ingest_addr)] once every replica is registered."""
        while True:
            rows = [
                r for r in router.table()["replicas"]
                if r.get("alive") and r.get("url")
                and (not framed or r.get("ingest"))
            ]
            if len(rows) >= n_replicas:
                return [(r["url"], r.get("ingest", "")) for r in rows]
            if time.time() > deadline:
                raise TimeoutError("replicas never registered their endpoints")
            time.sleep(0.2)

    def run_phase(framed, kill=False, phase_timeout=600.0):
        root = tempfile.mkdtemp(prefix="bench-ing-")
        deadline = time.time() + phase_timeout
        procs, logs = spawn_replicas(root, framed)
        sent = [0]          # rows acked, all streamers (under count_lock)
        count_lock = threading.Lock()
        errors = []
        try:
            router = ReplicaRouter(root)
            eps = endpoints(router, framed, deadline)

            def make_store(ep):
                url, addr = ep
                if framed:
                    return FramedObservationStore(addr, base_url=url, retries=3)
                return HttpRemoteObservationStore(url, retries=3)

            trials = trial_names()
            shards = [trials[s::n_streamers] for s in range(n_streamers)]

            def stream(shard_idx):
                """One streamer = the flusher of many trial processes: each
                report is one at-least-once batch pushed to the trial's home
                replica, rerouted to a survivor when the home dies."""
                stores = [None] * len(eps)
                try:
                    for trial in shards[shard_idx]:
                        home = hash(trial) % len(eps)
                        rows = expected_rows(trial)
                        for step in range(n_reports):
                            batch = [
                                MetricLog(ts, name, value)
                                for ts, name, value in rows[2 * step: 2 * step + 2]
                            ]
                            for attempt in range(len(eps)):
                                target = (home + attempt) % len(eps)
                                if stores[target] is None:
                                    stores[target] = make_store(eps[target])
                                try:
                                    stores[target].report_observation_log(trial, batch)
                                    break
                                except RpcError:
                                    if attempt == len(eps) - 1:
                                        raise  # every replica refused
                            with count_lock:
                                sent[0] += len(batch)
                except BaseException as e:  # surfaced after join
                    errors.append(f"streamer {shard_idx}: {type(e).__name__}: {e}")
                finally:
                    for s in stores:
                        if s is not None:
                            try:
                                s.close()
                            except Exception:
                                pass

            # warmup outside the measured window: first-touch SQLite DDL and
            # one connection per endpoint per protocol
            warm = make_store(eps[0])
            warm.report_observation_log(
                "ing-warmup", [MetricLog(1.0, "warm", "0.0")]
            )
            warm.close()

            total_rows = n_exps * n_trials * n_reports * 2
            t0 = time.time()
            threads = [
                threading.Thread(target=stream, args=(s,), daemon=True)
                for s in range(n_streamers)
            ]
            for t in threads:
                t.start()
            victim = None
            if kill:
                # SIGKILL one replica once the stream is well established;
                # its unacked batches are resent to the survivors
                while time.time() < deadline:
                    with count_lock:
                        done = sent[0]
                    if done >= total_rows // 4:
                        victim = f"r{n_replicas - 1}"
                        procs[victim].send_signal(_signal.SIGKILL)
                        procs[victim].wait()
                        break
                    time.sleep(0.05)
            for t in threads:
                t.join(timeout=max(0.0, deadline - time.time()))
                assert not t.is_alive(), f"streamer hung; see {root}/r*.log"
            wall = time.time() - t0
            assert not errors, f"streamers failed: {errors[:3]} (see {root}/r*.log)"

            # offline verification against the shared WAL store: the full
            # deterministic row set, exactly once, bit-identical
            store = SqliteObservationStore(os.path.join(root, "observations.db"))
            lost, mismatched = [], []
            try:
                for trial in trials:
                    got = sorted(
                        (r.timestamp, r.metric_name, r.value)
                        for r in store.get_observation_log(trial)
                    )
                    want = sorted(expected_rows(trial))
                    if len(got) != len(want):
                        lost.append((trial, len(got), len(want)))
                    elif got != want:
                        mismatched.append(trial)
            finally:
                store.close()
            assert not lost, f"lost/duplicated rows: {lost[:5]}"
            assert not mismatched, f"rows not bit-identical: {mismatched[:5]}"
            return {
                "root": root,
                "wall": wall,
                "rows_per_sec": total_rows / wall,
                "victim": victim,
            }
        finally:
            for proc in procs.values():
                if proc.poll() is None:
                    proc.terminate()
            for proc in procs.values():
                if proc.poll() is None:
                    try:
                        proc.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        proc.kill()
            for out in logs:
                out.close()

    # phase A: the PR 15 HTTP/JSON wire — the baseline the framed plane
    # must beat on the SAME workload
    json_phase = run_phase(framed=False)
    results = {"json": json_phase}
    speedup = None
    if not smoke:
        # phase B: framed ingest, fault-free — the throughput claim
        framed_phase = run_phase(framed=True)
        results["framed"] = framed_phase
        speedup = framed_phase["rows_per_sec"] / json_phase["rows_per_sec"]
        assert speedup >= 5.0, (
            f"framed ingest scaled only {speedup:.2f}x over the JSON wire "
            f"(>= 5x required): {json_phase['rows_per_sec']:.0f} -> "
            f"{framed_phase['rows_per_sec']:.0f} rows/s"
        )
    # phase C: framed ingest + mid-stream SIGKILL — the zero-loss claim
    # (row-set verification happens inside run_phase)
    chaos = run_phase(framed=True, kill=True)
    results["chaos"] = chaos
    assert chaos["victim"] is not None, "kill trigger never fired"
    for phase in results.values():
        shutil.rmtree(phase["root"], ignore_errors=True)
    out = {
        "experiments": n_exps,
        "trials_per_experiment": n_trials,
        "reports_per_trial": n_reports,
        "streamers": n_streamers,
        "replicas": n_replicas,
        "rows_per_sec_json": round(json_phase["rows_per_sec"], 1),
        "rows_per_sec_framed_chaos": round(chaos["rows_per_sec"], 1),
        "sigkill_victim": chaos["victim"],
        "lost_observations": 0,
        "bit_identical": True,
        "smoke": smoke,
    }
    if speedup is not None:
        out["rows_per_sec_framed"] = round(results["framed"]["rows_per_sec"], 1)
        out["speedup"] = round(speedup, 3)
        out["speedup_target"] = 5.0
    return out


SCENARIOS = {
    "obslog_report_throughput": _bench_obslog_report_throughput,
    "obslog_fold_latency": _bench_obslog_fold_latency,
    "tracing_overhead": _bench_tracing_overhead,
    "step_stats_overhead": _bench_step_stats_overhead,
    "telemetry_overhead": _bench_telemetry_overhead,
    "check_latency": _bench_check_latency,
    "analyze_latency": _bench_analyze_latency,
    "compile_amortization": _bench_compile_amortization,
    "pbt_fused_throughput": _bench_pbt_fused_throughput,
    "suggestion_throughput": _bench_suggestion_throughput,
    "suggestion_pipeline_latency": _bench_suggestion_pipeline_latency,
    "asha_device_seconds": _bench_asha_device_seconds,
    "bohb_convergence": _bench_bohb_convergence,
    "device_chaos_recovery": _bench_device_chaos_recovery,
    "controller_kill_recovery": _bench_controller_kill_recovery,
    "control_plane_scaling": _bench_control_plane_scaling,
    "multi_tenant_scaling": _bench_multi_tenant_scaling,
    "ingest_throughput": _bench_ingest_throughput,
}


if __name__ == "__main__":
    if len(sys.argv) < 2 or sys.argv[1] not in SCENARIOS:
        print(
            "usage: python bench.py <scenario> [--smoke] [--distributed]\n"
            "scenarios:\n  " + "\n  ".join(SCENARIOS),
            file=sys.stderr,
        )
        sys.exit(2)
    kwargs = {"smoke": "--smoke" in sys.argv[2:]}
    if "--distributed" in sys.argv[2:]:
        kwargs["distributed"] = True  # tracing_overhead only (ISSUE 19)
    result = SCENARIOS[sys.argv[1]](**kwargs)
    print(json.dumps({"metric": sys.argv[1], **result}))
