"""AOT compile service (ISSUE 8): admission-time AOT compilation on the
worker pool, fingerprint-keyed executable registry, warm-first /
compile-gated dispatch, failure quarantine, and the byte-identical disabled
path — all under JAX_PLATFORMS=cpu."""

import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from katib_tpu.analysis import program
from katib_tpu.analysis.program import ProgramProbe
from katib_tpu.api.spec import (
    AlgorithmSpec,
    ExperimentSpec,
    FeasibleSpace,
    ObjectiveSpec,
    ObjectiveType,
    ParameterAssignment,
    ParameterSpec,
    ParameterType,
    TrialTemplate,
)
from katib_tpu.api.status import Experiment, Trial
from katib_tpu.compilesvc.service import (
    STATE_COMPILING,
    STATE_FAILED,
    STATE_PENDING,
    STATE_WARM,
    CompileEntry,
    CompileService,
)
from katib_tpu.config import KatibConfig, load_config
from katib_tpu.controller.experiment import ExperimentController

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _semantic_on():
    from katib_tpu.compilesvc.service import clear_process_cache

    program.set_enabled(True)
    program.clear_cache()
    clear_process_cache()  # each test's compile counters start from zero
    yield
    program.set_enabled(True)
    program.clear_cache()
    clear_process_cache()


# -- fixtures: two distinct probed trial programs ----------------------------

INLINE_COMPILES = {"n": 0}  # trials that ran without a warm executable


def svc_trial_a(assignments, ctx=None):
    lr = jnp.float32(float(assignments["lr"]))
    if ctx is not None and ctx.compiled_program is not None:
        val = float(ctx.compiled_program.executable(lr))
    else:
        INLINE_COMPILES["n"] += 1
        val = float(lr) * 2.0
    if ctx is not None:
        ctx.report(loss=val)


def _probe_a(assignments):
    av = jax.ShapeDtypeStruct((), jnp.float32)
    return ProgramProbe(fn=lambda lr: lr * 2.0, args=(av,), hyperparams={"lr": av})


svc_trial_a.abstract_program = _probe_a


def svc_trial_b(assignments, ctx=None):
    lr = jnp.float32(float(assignments["lr"]))
    val = float(lr) + 1.0
    if ctx is not None:
        ctx.report(loss=val)


def _probe_b(assignments):
    av = jax.ShapeDtypeStruct((), jnp.float32)
    return ProgramProbe(fn=lambda lr: lr + 1.0, args=(av,), hyperparams={"lr": av})


svc_trial_b.abstract_program = _probe_b


def _spec(name, fn, lrs, parallel=None):
    return ExperimentSpec(
        name=name,
        parameters=[
            ParameterSpec("lr", ParameterType.DISCRETE, FeasibleSpace(list=lrs))
        ],
        objective=ObjectiveSpec(
            type=ObjectiveType.MINIMIZE, objective_metric_name="loss"
        ),
        algorithm=AlgorithmSpec("grid"),
        trial_template=TrialTemplate(function=fn),
        max_trial_count=len(lrs),
        parallel_trial_count=parallel or len(lrs),
    )


def _trial(exp_name, name, **assignments):
    return Trial(
        name=name,
        experiment_name=exp_name,
        parameter_assignments=[
            ParameterAssignment(k, v) for k, v in assignments.items()
        ],
    )


def _config(**runtime_kw):
    cfg = KatibConfig()
    cfg.runtime.telemetry = False
    cfg.runtime.tracing = False
    for k, v in runtime_kw.items():
        setattr(cfg.runtime, k, v)
    return cfg


def _controller(config, devices=1):
    return ExperimentController(
        root_dir=None, persist=False, devices=list(range(devices)), config=config
    )


def _wait(predicate, timeout=20.0, interval=0.01):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


# -- service unit behavior ---------------------------------------------------

def test_request_compiles_once_and_turns_warm():
    svc = CompileService(workers=1, timeout_seconds=30)
    svc.start()
    try:
        exp = Experiment(spec=_spec("svc-warm", svc_trial_a, ["0.1", "0.2"]))
        keys = [
            svc.request(exp, _trial("svc-warm", f"t{i}", lr=v))
            for i, v in enumerate(["0.1", "0.2"])
        ]
        assert keys[0] is not None and keys[0] == keys[1]
        assert _wait(lambda: svc.state_for_key(keys[0]) == STATE_WARM), (
            svc.registry_snapshot()
        )
        stats = svc.stats()
        assert stats["compiled"] == 1 and stats["traces"] == 1
        warm = svc.warm_executable_for(exp.spec, _trial("svc-warm", "t9", lr="0.3"))
        assert warm is not None and warm.fingerprint.startswith("ktfp-")
        # the executable is the real AOT-compiled program
        assert float(warm.executable(jnp.float32(3.0))) == 6.0
        # fingerprint matches the analysis fingerprint byte-for-byte (same
        # canonical jaxpr) — the registry and `katib-tpu analyze` agree
        assert warm.fingerprint == program.analyze_spec(exp.spec).fingerprint
    finally:
        svc.stop()


def test_prewarm_enqueues_baseline_group_at_admission():
    svc = CompileService(workers=1, timeout_seconds=30)
    svc.start()
    try:
        spec = _spec("svc-prewarm", svc_trial_a, ["0.1", "0.5"])
        key = svc.prewarm(spec)
        assert key is not None
        assert _wait(lambda: svc.state_for_key(key) == STATE_WARM)
        # a later trial of the sweep lands on the prewarmed group (runtime-
        # scalar parameter: same dispatch group as the baseline)
        exp = Experiment(spec=spec)
        assert svc.request(exp, _trial("svc-prewarm", "t0", lr="0.5")) == key
        assert svc.stats()["compiled"] == 1
    finally:
        svc.stop()


def test_compile_queue_is_cost_ordered():
    """Big programs start first: the job queue pops by cost-model FLOPs
    descending, arrival order breaking ties."""
    from katib_tpu.compilesvc.service import _Job

    svc = CompileService(workers=1)  # not started: inspect the queue raw

    def job(target, cost):
        return _Job(
            key=target, experiment="e", target=target, builder=None,
            assignments={}, cost_flops=cost,
        )

    svc._enqueue(job("small", 10.0))
    svc._enqueue(job("big", 1e9))
    svc._enqueue(job("mid", 1e6))
    svc._enqueue(job("mid-later", 1e6))
    order = [svc._queue.get()[2].target for _ in range(4)]
    assert order == ["big", "mid", "mid-later", "small"]


def test_unanalyzable_template_is_ignored():
    svc = CompileService(workers=1)
    svc.start()
    try:
        spec = _spec("svc-cmd", svc_trial_a, ["0.1"])
        spec.trial_template = TrialTemplate(command=["true"])
        exp = Experiment(spec=spec)
        assert svc.request(exp, _trial("svc-cmd", "t0", lr="0.1")) is None
        assert svc.prewarm(spec) is None
        assert svc.stats()["entries"] == 0
    finally:
        svc.stop()


def test_failed_compile_quarantined_with_exactly_one_event():
    """A failing AOT compile fails ONCE: one job, one CompileFailed warning
    event, entry quarantined as `failed`, and later trials of the group
    neither re-enqueue nor re-fail — they fall back to inline compilation."""
    from katib_tpu.controller.events import EventRecorder

    events = EventRecorder()
    svc = CompileService(workers=1, timeout_seconds=30, events=events)
    calls = {"n": 0}

    def _boom(job):
        calls["n"] += 1
        raise RuntimeError("synthetic XLA failure")

    svc._compile_probe = _boom
    svc.start()
    try:
        exp = Experiment(
            spec=_spec("svc-fail", svc_trial_a, ["0.1", "0.2", "0.3"])
        )
        key = None
        for i, v in enumerate(["0.1", "0.2", "0.3"]):
            key = svc.request(exp, _trial("svc-fail", f"t{i}", lr=v))
        assert _wait(lambda: svc.state_for_key(key) == STATE_FAILED)
        # give any (buggy) second job a chance to run, then pin the counts
        time.sleep(0.2)
        assert calls["n"] == 1
        failures = [e for e in events.list_all() if e.reason == "CompileFailed"]
        assert len(failures) == 1
        assert "quarantined" in failures[0].message
        assert failures[0].event_type == "Warning"
        # quarantined: no executable is ever handed out for this group
        assert svc.warm_executable_for(exp.spec, _trial("svc-fail", "t9", lr="0.2")) is None
        snap = svc.registry_snapshot()
        assert snap["entries"][0]["state"] == STATE_FAILED
        assert snap["entries"][0]["error"]
    finally:
        svc.stop()


def test_compile_timeout_quarantines_and_isolates_worker():
    """A wedged compile (hung XLA / backend init) hits the per-compile
    timeout: the inner thread is abandoned, the entry is quarantined, and
    the worker pool keeps serving new jobs."""
    release = threading.Event()
    svc = CompileService(workers=1, timeout_seconds=0.2)
    real_compile = svc._compile_probe

    def _wedge_then_real(job):
        if job.experiment == "svc-hang":
            release.wait(30)  # simulated wedge, far past the timeout
            raise RuntimeError("unreachable under the timeout")
        return real_compile(job)

    svc._compile_probe = _wedge_then_real
    svc.start()
    try:
        hang = Experiment(spec=_spec("svc-hang", svc_trial_b, ["0.1"]))
        key_hang = svc.request(hang, _trial("svc-hang", "t0", lr="0.1"))
        assert _wait(lambda: svc.state_for_key(key_hang) == STATE_FAILED)
        # the pool survived the wedge: a healthy job still compiles
        ok = Experiment(spec=_spec("svc-ok", svc_trial_a, ["0.1"]))
        key_ok = svc.request(ok, _trial("svc-ok", "t0", lr="0.1"))
        assert _wait(lambda: svc.state_for_key(key_ok) == STATE_WARM)
    finally:
        release.set()
        svc.stop()


def svc_trial_a_twin(assignments, ctx=None):
    """Distinct ``def`` (distinct template digest, so a distinct dispatch
    group) whose probe lowers to the SAME program as svc_trial_a — the
    fingerprint-dedup fixture."""
    svc_trial_a(assignments, ctx)


svc_trial_a_twin.abstract_program = _probe_a


def test_twin_fingerprint_reuses_executable():
    """Two dispatch groups whose templates lower to the same program share
    one executable: the second group's job traces, finds the warm twin by
    fingerprint, and skips .compile()."""
    svc = CompileService(workers=1, timeout_seconds=30)
    svc.start()
    try:
        spec1 = _spec("svc-twin1", svc_trial_a, ["0.1"])
        spec2 = _spec("svc-twin2", svc_trial_a_twin, ["0.9"])
        k1 = svc.prewarm(spec1)
        assert _wait(lambda: svc.state_for_key(k1) == STATE_WARM)
        k2 = svc.prewarm(spec2)
        assert k2 is not None and k2 != k1  # distinct groups (distinct defs)
        assert _wait(lambda: svc.state_for_key(k2) == STATE_WARM)
        stats = svc.stats()
        assert stats["compiled"] == 1  # second group reused the warm twin
        assert stats["traces"] == 2   # ...but was traced to prove equality
        snap = {e["key"]: e for e in svc.registry_snapshot()["entries"]}
        fps = {e["fingerprint"] for e in snap.values()}
        assert len(fps) == 1  # one fingerprint, two group keys
    finally:
        svc.stop()


# -- dispatch ordering + gate ------------------------------------------------

def _scheduler(svc=None, devices=1, gate=0.0):
    from katib_tpu.controller.scheduler import TrialScheduler
    from katib_tpu.db.state import ExperimentStateStore
    from katib_tpu.db.store import InMemoryObservationStore

    return TrialScheduler(
        ExperimentStateStore(None),
        InMemoryObservationStore(),
        devices=list(range(devices)),
        compile_service=svc,
        compile_gate_seconds=gate,
    )


def _entries(*pairs):
    from katib_tpu.controller import fairshare as fs

    return [
        fs.QueueEntry(
            exp=exp, trials=[t], needed=1, requested=1, seq=i, enqueued_at=0.0
        )
        for i, (exp, t) in enumerate(pairs)
    ]


def test_warm_groups_dispatch_before_cold_groups():
    """Warm-hit vs cold-miss ordering: the group whose executable is WARM in
    the registry jumps ahead of a cold group that arrived first; within each
    group arrival order is preserved."""
    svc = CompileService(workers=1, timeout_seconds=30)
    svc.start()
    try:
        sched = _scheduler(svc)
        exp_a = Experiment(spec=_spec("ord-warm", svc_trial_a, ["0.1", "0.2"]))
        exp_b = Experiment(spec=_spec("ord-cold", svc_trial_b, ["0.1", "0.2"]))
        key_a = svc.prewarm(exp_a.spec)
        assert _wait(lambda: svc.state_for_key(key_a) == STATE_WARM)
        # hold B cold: manufacture a pending entry so the service has an
        # opinion without compiling
        key_b = program.dispatch_group_key(exp_b.spec, _trial("ord-cold", "b1", lr="0.1"))
        with svc._lock:
            svc._by_key[key_b] = CompileEntry(
                key=key_b, experiment="ord-cold", target="b", state=STATE_PENDING
            )
        entries = _entries(
            (exp_b, _trial("ord-cold", "b1", lr="0.1")),
            (exp_a, _trial("ord-warm", "a1", lr="0.1")),
            (exp_b, _trial("ord-cold", "b2", lr="0.2")),
            (exp_a, _trial("ord-warm", "a2", lr="0.2")),
        )
        ordered = sched._fingerprint_grouped(entries)
        assert [e.trials[0].name for e in ordered] == ["a1", "a2", "b1", "b2"]
        # without the service the PR 7 ordering is untouched: groups at
        # first-arrival position — cold B first
        sched_plain = _scheduler(None)
        ordered = sched_plain._fingerprint_grouped(entries)
        assert [e.trials[0].name for e in ordered] == ["b1", "b2", "a1", "a2"]
    finally:
        svc.stop()


def test_disabled_service_is_byte_identical_to_legacy_dispatch():
    """KATIB_TPU_COMPILE_SERVICE=0 (or a stopped service) restores the PR 7
    legacy walk exactly: same grouped order, FIFO identity without keys, no
    gate holds, no registry consults."""
    exp_a = Experiment(spec=_spec("leg-a", svc_trial_a, ["0.1", "0.2"]))
    exp_b = Experiment(spec=_spec("leg-b", svc_trial_b, ["0.1", "0.2"]))
    entries = _entries(
        (exp_a, _trial("leg-a", "a1", lr="0.1")),
        (exp_b, _trial("leg-b", "b1", lr="0.1")),
        (exp_a, _trial("leg-a", "a2", lr="0.2")),
        (exp_b, _trial("leg-b", "b2", lr="0.2")),
    )
    legacy = _scheduler(None)._fingerprint_grouped(entries)
    stopped = CompileService(workers=1)  # never started -> inactive
    with_stopped = _scheduler(stopped, gate=5.0)._fingerprint_grouped(entries)
    assert [e.trials[0].name for e in legacy] == ["a1", "a2", "b1", "b2"]
    assert [e.trials[0].name for e in with_stopped] == [
        e.trials[0].name for e in legacy
    ]
    # FIFO identity when analysis contributes no keys at all
    program.set_enabled(False)
    try:
        assert [
            e.trials[0].name
            for e in _scheduler(stopped, gate=5.0)._fingerprint_grouped(entries)
        ] == ["a1", "b1", "a2", "b2"]
    finally:
        program.set_enabled(True)


def test_env_var_disables_service_construction(monkeypatch, tmp_path):
    monkeypatch.setenv("KATIB_TPU_COMPILE_SERVICE", "0")
    cfg = load_config()
    assert cfg.runtime.compile_service is False
    cfg.runtime.telemetry = False
    cfg.runtime.tracing = False
    ctrl = ExperimentController(
        root_dir=str(tmp_path), devices=[0], config=cfg
    )
    try:
        assert ctrl.compile_service is None
        assert ctrl.scheduler.compile_service is None
    finally:
        ctrl.close()


def test_compile_knob_env_overrides(monkeypatch):
    monkeypatch.setenv("KATIB_TPU_COMPILE_WORKERS", "5")
    monkeypatch.setenv("KATIB_TPU_COMPILE_GATE_SECONDS", "7.5")
    monkeypatch.setenv("KATIB_TPU_COMPILE_TIMEOUT_SECONDS", "33")
    monkeypatch.setenv("KATIB_TPU_XLA_CACHE_MIN_COMPILE_SECONDS", "0.25")
    cfg = load_config()
    assert cfg.runtime.compile_workers == 5
    assert cfg.runtime.compile_gate_seconds == 7.5
    assert cfg.runtime.compile_timeout_seconds == 33.0
    assert cfg.runtime.xla_cache_min_compile_seconds == 0.25


def test_gate_timeout_falls_back_to_inline_compile():
    """A unit whose program never turns warm is held at most
    compile_gate_seconds, then dispatches and compiles inline; the queue
    span records that the wait was the compile gate, not chip contention
    (Perfetto satellite)."""
    cfg = _config(compile_gate_seconds=0.4, tracing=True)
    ctrl = _controller(cfg)
    stall = threading.Event()
    svc = ctrl.compile_service

    def _never_finishes(job):
        stall.wait(60)
        raise RuntimeError("unreachable")

    svc._compile_probe = _never_finishes
    INLINE_COMPILES["n"] = 0
    try:
        spec = _spec("gate-to", svc_trial_a, ["0.1", "0.2"], parallel=2)
        ctrl.create_experiment(spec)
        t0 = time.time()
        exp = ctrl.run("gate-to", timeout=60)
        elapsed = time.time() - t0
        assert exp.status.is_succeeded
        assert INLINE_COMPILES["n"] == 2  # no warm executable: inline path
        assert elapsed >= 0.35, f"gate never held ({elapsed:.3f}s)"
        # queue spans of the gated trials carry the satellite attributes
        gated = []
        for t in ctrl.state.list_trials("gate-to"):
            trace = ctrl.tracer.trial_trace("gate-to", t.name)
            for s in trace["spans"]:
                if s["name"] == "queue_wait" and s["attrs"].get("compileGated"):
                    gated.append(s)
                    assert s["attrs"]["compileGateSeconds"] >= 0.3
            names = [s["name"] for s in trace["spans"]]
            assert "compile_gate" in names
        assert gated, "no queue_wait span recorded the compile gate"
    finally:
        stall.set()
        ctrl.close()


def test_gate_releases_early_when_compile_finishes():
    """The gate is a hold, not a sleep: when the AOT compile lands inside
    the window, dispatch resumes immediately (service listener) and the
    trial receives the warm executable."""
    cfg = _config(compile_gate_seconds=20.0)
    ctrl = _controller(cfg)
    INLINE_COMPILES["n"] = 0
    try:
        spec = _spec("gate-fast", svc_trial_a, ["0.1", "0.2", "0.3"], parallel=3)
        ctrl.create_experiment(spec)
        t0 = time.time()
        exp = ctrl.run("gate-fast", timeout=60)
        elapsed = time.time() - t0
        assert exp.status.is_succeeded
        assert elapsed < 15.0, "gate degenerated into a full-window sleep"
        assert INLINE_COMPILES["n"] == 0  # every trial got the executable
    finally:
        ctrl.close()


# -- the acceptance sweep ----------------------------------------------------

def test_16_trial_sweep_compiles_once_in_service():
    """Acceptance (ISSUE 8): a 16-trial all-runtime-scalar sweep compiles
    its shared program exactly once INSIDE the CompileService (trace
    counter), dispatch never blocks inline on XLA while the gate is on
    (every trial receives the warm executable), and the one executable
    serves all 16 trials."""
    cfg = _config(compile_gate_seconds=10.0)
    ctrl = _controller(cfg)
    INLINE_COMPILES["n"] = 0
    lrs = [format(0.05 * (i + 1), ".4f") for i in range(16)]
    try:
        spec = _spec("sweep16", svc_trial_a, lrs, parallel=16)
        ctrl.create_experiment(spec)
        exp = ctrl.run("sweep16", timeout=120)
        assert exp.status.is_succeeded
        assert len(ctrl.state.list_trials("sweep16")) == 16
        stats = ctrl.compile_service.stats()
        # the trace counter: the shared program was traced (and compiled)
        # exactly once in the service across the whole sweep
        assert stats["traces"] == 1, stats
        assert stats["compiled"] == 1, stats
        # dispatch never fell back to inline XLA: all 16 used the executable
        assert INLINE_COMPILES["n"] == 0
        snap = ctrl.compile_service.registry_snapshot()
        entry = snap["entries"][0]
        assert entry["state"] == STATE_WARM
        assert entry["fingerprint"].startswith("ktfp-")
        assert entry["trialsServed"] == 16
    finally:
        ctrl.close()


def test_process_cache_shares_executables_across_service_instances():
    """Repeat experiments / multiple controllers in one process: a second
    CompileService tracing a program the first already compiled adopts the
    executable from the process-level fingerprint cache — no second
    .compile()."""
    svc1 = CompileService(workers=1, timeout_seconds=30)
    svc1.start()
    try:
        spec = _spec("pc-one", svc_trial_a, ["0.1"])
        k1 = svc1.prewarm(spec)
        assert _wait(lambda: svc1.state_for_key(k1) == STATE_WARM)
        assert svc1.stats()["compiled"] == 1
    finally:
        svc1.stop()
    svc2 = CompileService(workers=1, timeout_seconds=30)
    svc2.start()
    try:
        spec2 = _spec("pc-two", svc_trial_a, ["0.7"])
        k2 = svc2.prewarm(spec2)
        assert _wait(lambda: svc2.state_for_key(k2) == STATE_WARM)
        stats = svc2.stats()
        assert stats["traces"] == 1 and stats["compiled"] == 0  # adopted
        warm = svc2.warm_executable_for(
            Experiment(spec=spec2).spec, _trial("pc-two", "t0", lr="0.7")
        )
        assert warm is not None
        assert float(warm.executable(jnp.float32(2.0))) == 4.0
    finally:
        svc2.stop()


def test_compile_service_span_joins_trial_trace():
    """The worker's compile_service span lands in the requesting trial's
    trace — 'where did this trial's wall-clock go' now answers 'the
    service was compiling your program' explicitly."""
    from katib_tpu.tracing import Tracer

    tracer = Tracer(enabled=True)
    svc = CompileService(workers=1, timeout_seconds=30, tracer=tracer)
    gate = threading.Event()
    real_compile = svc._compile_probe

    def _slow(job):
        gate.wait(10)  # hold the compile until the trial has requested
        return real_compile(job)

    svc._compile_probe = _slow
    svc.start()
    try:
        spec = _spec("span-join", svc_trial_a, ["0.1"])
        exp = Experiment(spec=spec)
        root = tracer.begin_trial("span-join", "t0")
        key = svc.request(
            exp, _trial("span-join", "t0", lr="0.1"),
            trace=(root.trace_id, root.span_id),
        )
        gate.set()
        assert _wait(lambda: svc.state_for_key(key) == STATE_WARM)
        assert _wait(
            lambda: any(
                s["name"] == "compile_service" and s["end"] is not None
                for s in (tracer.trial_trace("span-join", "t0") or {"spans": []})["spans"]
            ),
            timeout=5,
        )
        spans = {
            s["name"]: s for s in tracer.trial_trace("span-join", "t0")["spans"]
        }
        cs_span = spans["compile_service"]
        assert cs_span["parentId"] == root.span_id
        assert cs_span["attrs"]["fingerprint"].startswith("ktfp-")
        assert cs_span["attrs"]["outcome"] == "warm"
    finally:
        gate.set()
        svc.stop()


# -- registry persistence + CLI ----------------------------------------------

def test_registry_persisted_and_cli_compile_renders_it(tmp_path, capsys):
    from katib_tpu.cli import main

    cfg = _config(compile_gate_seconds=10.0)
    ctrl = ExperimentController(
        root_dir=str(tmp_path), devices=[0], config=cfg
    )
    try:
        spec = _spec("cli-reg", svc_trial_a, ["0.1", "0.2"], parallel=2)
        ctrl.create_experiment(spec)
        exp = ctrl.run("cli-reg", timeout=60)
        assert exp.status.is_succeeded
    finally:
        ctrl.close()
    path = tmp_path / "compilesvc" / "registry.json"
    assert path.exists()
    snap = json.loads(path.read_text())
    assert snap["entries"][0]["state"] == STATE_WARM

    rc = main(["--root", str(tmp_path), "compile"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "ktfp-" in out and "warm" in out

    # no snapshot -> actionable error, exit 1
    rc = main(["--root", str(tmp_path / "nope"), "compile"])
    err = capsys.readouterr().err
    assert rc == 1 and "no persisted compile registry" in err


# -- backend-init robustness (satellite) -------------------------------------

def test_bounded_backend_probe_times_out_and_emits_once(monkeypatch):
    from katib_tpu.controller.events import EventRecorder
    from katib_tpu.utils import backend

    backend.reset_probe_state()
    release = threading.Event()

    def _wedged():
        release.wait(30)
        return []

    monkeypatch.setattr(jax, "local_devices", _wedged)
    events = EventRecorder()
    try:
        t0 = time.time()
        out = backend.bounded_local_devices(
            timeout_seconds=0.15, retries=2, backoff_seconds=0.01, events=events
        )
        assert out is None
        assert time.time() - t0 < 5.0  # bounded, never the 30s wedge
        # quarantined: the second call answers immediately, no second event
        t1 = time.time()
        assert backend.bounded_local_devices(events=events) is None
        assert time.time() - t1 < 0.05
        failed = [e for e in events.list_all() if e.reason == "BackendInitFailed"]
        assert len(failed) == 1 and failed[0].event_type == "Warning"
    finally:
        release.set()
        backend.reset_probe_state()


def test_bounded_backend_probe_success_path():
    from katib_tpu.utils import backend

    backend.reset_probe_state()
    try:
        devices = backend.bounded_local_devices(timeout_seconds=30)
        assert devices  # CPU backend answers
        # verdict cached: the follow-up is a direct call
        assert backend.bounded_local_devices() == devices
    finally:
        backend.reset_probe_state()


@pytest.mark.parametrize("expected", [True, False], ids=["chip-expected", "cpu-held"])
def test_failed_probe_raises_where_a_chip_was_expected(monkeypatch, expected):
    """A probe that fails on a host that has a chip raises; only a process
    held to the CPU anyway gets None (and one warning event)."""
    from katib_tpu.utils import backend, compilation

    def _dead():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "local_devices", _dead)
    monkeypatch.setattr(compilation, "accelerator_expected", lambda: expected)
    backend.reset_probe_state()
    try:
        for _ in range(2):  # the cached verdict answers the same way
            if expected:
                with pytest.raises(backend.BackendUnavailable, match="refusing"):
                    backend.bounded_local_devices(retries=1)
            else:
                assert backend.bounded_local_devices(retries=1) is None
        with pytest.raises(backend.BackendUnavailable):
            backend.require_devices(retries=1)
    finally:
        backend.reset_probe_state()


def test_readers_never_initialize_a_backend(monkeypatch):
    """Telemetry, the admission pre-flight and step statistics look only at
    a backend that is already up: the process that initializes the TPU
    backend owns the chip, and a controller of subprocess trials must not."""
    from jax._src import xla_bridge

    from katib_tpu.analysis.program import device_capacity_bytes
    from katib_tpu.telemetry import read_device_memory
    from katib_tpu.utils import backend

    def _boom(*_a, **_k):
        raise AssertionError("a reader initialized the backend")

    monkeypatch.setattr(xla_bridge, "backends_are_initialized", lambda: False)
    monkeypatch.setattr(jax, "local_devices", _boom)
    monkeypatch.setattr(jax, "devices", _boom)
    assert backend.initialized_local_devices() is None
    assert backend.holds_accelerator() is False
    assert read_device_memory() == []
    assert device_capacity_bytes() is None


def test_holds_accelerator_reads_the_platform(monkeypatch):
    from katib_tpu.utils import backend

    class Dev:
        def __init__(self, platform):
            self.platform = platform

    monkeypatch.setattr(backend, "initialized_local_devices", lambda: [Dev("tpu")])
    assert backend.holds_accelerator() is True
    monkeypatch.setattr(backend, "initialized_local_devices", lambda: [Dev("cpu")])
    assert backend.holds_accelerator() is False


def test_xla_cache_min_compile_env_parsing(monkeypatch):
    from katib_tpu.utils.compilation import min_compile_seconds_from_env

    monkeypatch.delenv("KATIB_TPU_XLA_CACHE_MIN_COMPILE_SECONDS", raising=False)
    assert min_compile_seconds_from_env() == 0.0
    monkeypatch.setenv("KATIB_TPU_XLA_CACHE_MIN_COMPILE_SECONDS", "1.5")
    assert min_compile_seconds_from_env() == 1.5
    monkeypatch.setenv("KATIB_TPU_XLA_CACHE_MIN_COMPILE_SECONDS", "junk")
    assert min_compile_seconds_from_env() == 0.0  # malformed keeps default


# -- lockgraph stress --------------------------------------------------------

def test_lockgraph_stress_with_worker_pool_active(tmp_path):
    """Dynamic lock-order check (ISSUE 6 plumbing) with the compile plane
    live: worker-pool compiles, service listeners re-entering the dispatch
    pass, gate holds/releases and warm handoffs all cross the scheduler,
    service, tracer and metrics locks concurrently — any ordering cycle
    fails the test as a potential deadlock."""
    from katib_tpu.analysis import lockgraph

    with lockgraph.instrument() as lock_order:
        cfg = _config(compile_gate_seconds=2.0, tracing=True)
        ctrl = ExperimentController(
            root_dir=str(tmp_path), devices=list(range(4)), config=cfg
        )
        try:
            lrs = [format(0.05 * (i + 1), ".4f") for i in range(8)]
            ctrl.create_experiment(_spec("lg-a", svc_trial_a, lrs, parallel=4))
            ctrl.create_experiment(_spec("lg-b", svc_trial_b, lrs, parallel=4))
            threads = [
                threading.Thread(target=ctrl.run, args=(name,), kwargs={"timeout": 90})
                for name in ("lg-a", "lg-b")
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            for name in ("lg-a", "lg-b"):
                exp = ctrl.state.get_experiment(name)
                assert exp.status.is_succeeded, (name, exp.status.message)
        finally:
            ctrl.close()
    lock_order.assert_no_cycles()
