"""What the trial runtime records about itself (PR 27): the step ledger on the
``steps`` span, the children of ``compile`` from JAX's own timers, the
``katib:*`` mirror on the profiler's clock — and that none of it exists where
tracing is off or jax is not imported."""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from katib_tpu import tracing
from katib_tpu.controller.experiment import ExperimentController
from katib_tpu.runtime.context import TrialContext
from katib_tpu.tracing import StepLedger, Tracer

from test_tracing import make_spec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE, LOWER, BACKEND = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class Clock:
    def __init__(self, at=100.0):
        self.at = at

    def __call__(self):
        return self.at

    def tick(self, seconds):
        self.at += seconds


def _interval(ledger, clock, steps=5, dispatch=0.002, wait=0.75, python=0.001,
              before_store=0.003, store=0.004):
    """One interval as a trial's loop makes it; returns its seconds."""
    t0 = clock()
    for _ in range(steps):
        clock.tick(python)
        t_call = clock()
        clock.tick(dispatch)
        ledger.stepped(t_call, clock())
    clock.tick(wait)                     # float(loss)
    t_entry = clock()
    clock.tick(before_store)             # tracer mark, heartbeat
    clock.tick(store)
    ledger.stored(store)
    ledger.reported(t_entry)
    return clock() - t0


# ---------------------------------------------------------------------------
# ledger arithmetic, with an injected clock
# ---------------------------------------------------------------------------

def test_the_parts_of_an_interval_sum_to_it():
    clock = Clock()
    ledger = StepLedger(clock=clock, wall=lambda: 1e9 + clock())
    ledger.stepped(clock(), clock())       # before the first report: the compile span's
    ledger.reported(clock())               # the first report opens the first interval
    assert not ledger.intervals and ledger.totals["steps"] == 0
    seconds = _interval(ledger, clock)
    (row,) = ledger.intervals
    i = dict(zip(StepLedger.FIELDS, row))
    assert i["t_end"] == pytest.approx(1e9 + clock())
    assert i["seconds"] == pytest.approx(seconds) == pytest.approx(0.772)
    assert i["steps"] == 5
    assert i["dispatch_s"] == pytest.approx(0.010)
    assert i["wait_s"] == pytest.approx(0.75)
    assert i["report_s"] == pytest.approx(0.007)
    assert i["store_s"] == pytest.approx(0.004)
    own_python = i["seconds"] - i["dispatch_s"] - i["wait_s"] - i["report_s"]
    assert own_python == pytest.approx(0.005)
    # a second interval starts where the first report left, not where it was entered
    clock.tick(0.5)
    assert _interval(ledger, clock, steps=0, wait=0.0) + 0.5 == pytest.approx(ledger.intervals[-1][1])
    assert ledger.intervals[-1][4] == 0.0          # no step called: nothing was waited for


def test_the_first_report_hands_the_earlier_steps_to_the_compile_span():
    clock = Clock()
    ledger = StepLedger(clock=clock, wall=clock)
    for _ in range(5):
        t = clock()
        clock.tick(3.0)
        ledger.stepped(t, clock())
    clock.tick(0.8)
    assert ledger.first_report(clock()) == {
        "steps": 5, "dispatch_s": pytest.approx(15.0), "wait_s": pytest.approx(0.8)}


def test_the_ring_stops_at_1024_and_the_totals_keep_counting():
    clock = Clock()
    ledger = StepLedger(clock=clock, wall=clock)
    ledger.reported(clock())
    total = sum(_interval(ledger, clock) for _ in range(1500))
    assert StepLedger.RING == 1024 == len(ledger.intervals)
    assert ledger.totals["steps"] == 7500
    assert ledger.totals["seconds"] == pytest.approx(total)
    assert ledger.totals["wait_s"] == pytest.approx(1500 * 0.75)
    attrs = ledger.attrs()
    assert attrs["interval_fields"] == list(StepLedger.FIELDS) and len(attrs["intervals"]) == 1024
    assert attrs["intervals"][-1][0] == pytest.approx(clock())     # the newest are kept
    json.dumps(attrs)


# ---------------------------------------------------------------------------
# the hooks in TrialContext
# ---------------------------------------------------------------------------

class _Reporter:
    store = None

    def __init__(self, fail_at=None):
        self.rows, self.fail_at = [], fail_at

    def report(self, **metrics):
        self.rows.append(metrics)
        if self.fail_at == len(self.rows):
            raise RuntimeError("unwound inside the store write")


def _bound_context(reporter=None):
    tracer = Tracer(enabled=True)
    ctx = TrialContext("t-1", "e", {}, reporter or _Reporter())
    root = tracer.begin_trial("e", "t-1")
    ctx.bind_trace(tracer, "e", root.trace_id, root.span_id)
    return tracer, ctx


def _spans(tracer, ctx):
    return {s.name: s for s in tracer.trace_spans("e", ctx.trace_id)}


def test_watch_step_is_the_identity_with_no_tracer():
    ctx = TrialContext("t", "e", {}, _Reporter())

    def step(x):
        return x

    assert ctx.watch_step(step) is step
    ctx.report(loss=1.0)                    # and a report is just the store write
    assert ctx.reporter.rows == [{"loss": 1.0}]


def test_watch_step_returns_what_the_step_returns_and_counts_it():
    tracer, ctx = _bound_context()
    calls = []

    def step(params, opt_state, *batch, scale=1):
        calls.append((params, opt_state, batch, scale))
        return params + 1, opt_state, 0.5 * scale

    watched = ctx.watch_step(step)
    assert watched is not step
    ctx._trace_fn_start()
    assert watched(1, "o", "tokens", "targets", scale=2) == (2, "o", 1.0)
    assert calls == [(1, "o", ("tokens", "targets"), 2)]
    with pytest.raises(ZeroDivisionError):
        ctx.watch_step(lambda: 1 / 0)()     # the step's own error, and it is not counted
    ctx.report(loss=1.0)
    for _ in range(3):
        for _ in range(5):
            watched(1, "o")
        ctx.report(loss=1.0)
    ctx._trace_fn_end()
    spans = _spans(tracer, ctx)
    assert spans["compile"].attrs["steps"] == 1 and spans["compile"].attrs["first_report"] is True
    steps = spans["steps"].attrs
    assert steps["steps"] == 15 and steps["reports"] == 4 and len(steps["intervals"]) == 3
    for row in steps["intervals"]:
        i = dict(zip(steps["interval_fields"], row))
        assert i["steps"] == 5
        assert 0 <= i["store_s"] <= i["report_s"]
        assert i["dispatch_s"] + i["wait_s"] + i["report_s"] <= i["seconds"] + 1e-9
        assert abs(i["t_end"] - time.time()) < 60
    assert steps["seconds"] == pytest.approx(sum(r[1] for r in steps["intervals"]))
    assert "intervals=[3]" in tracing.render_tree(list(spans.values())).replace("'", "").replace(": ", "=")


def test_a_report_that_unwinds_still_closes_its_interval():
    tracer, ctx = _bound_context(_Reporter(fail_at=2))
    ctx._trace_fn_start()
    watched = ctx.watch_step(lambda: None)
    ctx.report(loss=1.0)
    watched()
    with pytest.raises(RuntimeError):
        ctx.report(loss=2.0)
    ctx._trace_fn_end()
    steps = _spans(tracer, ctx)["steps"]
    assert steps.ended and steps.attrs["steps"] == 1 and len(steps.attrs["intervals"]) == 1
    assert steps.attrs["store_s"] > 0


def test_ctx_span_hangs_under_the_stage_that_is_open():
    tracer, ctx = _bound_context()
    ctx._trace_fn_start()
    with ctx.span("build"):
        pass
    ctx.report(loss=1.0)
    with ctx.span("evaluate", split="dev") as s:
        s.set(rows=3)
    ctx._trace_fn_end()
    spans = _spans(tracer, ctx)
    assert spans["build"].parent_id == spans["compile"].span_id
    assert spans["evaluate"].parent_id == spans["steps"].span_id
    assert spans["evaluate"].attrs == {"split": "dev", "rows": 3}
    with TrialContext("t", "e", {}, _Reporter()).span("anything") as s:
        s.set(ignored=True)                 # tracing off: the shared no-op


# ---------------------------------------------------------------------------
# compile children from JAX's timers, routed by thread
# ---------------------------------------------------------------------------

def _fire(event, start, end):
    import jax

    jax.monitoring.record_event_time_span(event, start, end, fun_name="step")


def test_compile_children_come_from_this_threads_events_only():
    import jax

    tracer, ctx = _bound_context()
    ctx._trace_fn_start()
    t0 = time.time()
    other = threading.Thread(target=_fire, args=(BACKEND, t0, t0 + 50.0))
    other.start()
    other.join(timeout=10)
    assert not other.is_alive()
    _fire(TRACE, t0 + 0.5, t0 + 0.9)                 # a jitted function inside the step's trace
    _fire(TRACE, t0 + 0.1, t0 + 1.0)                 # the step's trace, which encloses it
    _fire(TRACE, t0 + 1.0, t0 + 1.0002)              # too short for a span of its own
    _fire(LOWER, t0 + 1.0, t0 + 1.5)
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    _fire(BACKEND, t0 + 1.5, t0 + 9.5)
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    _fire(BACKEND, t0 + 9.5, t0 + 9.6)
    _fire(BACKEND, t0 + 9.7, t0 + 9.8)               # JAX reported no verdict for this one
    _fire("/jax/core/compile/something_else", t0, t0 + 99.0)
    ctx.report(loss=1.0)                             # ends compile: the route closes
    _fire(BACKEND, t0 + 20.0, t0 + 30.0)             # a compile in the steps phase is not a child
    ctx._trace_fn_end()
    spans = tracer.trace_spans("e", ctx.trace_id)
    compile_span = next(s for s in spans if s.name == "compile")
    children = [(s.name, round(s.start - t0, 4), round(s.end - t0, 4), s.attrs)
                for s in spans if s.parent_id == compile_span.span_id]
    assert children == [
        ("jaxpr_trace", 0.1, 1.0, {}),
        ("lower", 1.0, 1.5, {}),
        ("backend_compile", 1.5, 9.5, {"cache": "miss"}),
        ("backend_compile", 9.5, 9.6, {"cache": "hit"}),
        ("backend_compile", 9.7, 9.8, {}),
    ]
    assert compile_span.attrs["short_events"] == {"jaxpr_trace": [1, pytest.approx(0.0002, abs=1e-5)]}
    assert threading.get_ident() not in tracing._compile_routes
    assert tracing._compile_listener_installed


def test_a_trial_that_never_reports_keeps_its_compile_children():
    tracer, ctx = _bound_context()
    ctx._trace_fn_start()
    t0 = time.time()
    _fire(BACKEND, t0, t0 + 2.0)
    ctx._trace_fn_end()
    names = [s.name for s in tracer.trace_spans("e", ctx.trace_id)]
    assert "backend_compile" in names and "steps" not in names
    assert threading.get_ident() not in tracing._compile_routes


# ---------------------------------------------------------------------------
# both clocks
# ---------------------------------------------------------------------------

def test_one_thread_spans_are_mirrored_as_katib_annotations(monkeypatch):
    import jax

    seen = []

    class Note:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("leave", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Note)
    tracer, ctx = _bound_context()
    ctx._trace_fn_start()
    watched = ctx.watch_step(lambda: None)
    with ctx.span("build"):
        pass
    watched()
    ctx.report(loss=1.0)
    watched()
    ctx.report(loss=1.0)
    ctx._trace_fn_end()
    assert seen == [
        ("enter", "katib:compile"), ("enter", "katib:build"), ("leave", "katib:build"),
        ("enter", "katib:step"), ("leave", "katib:step"),
        ("leave", "katib:compile"), ("enter", "katib:steps"),
        ("enter", "katib:report"), ("enter", "katib:store_write"), ("leave", "katib:store_write"),
        ("leave", "katib:report"),
        ("enter", "katib:step"), ("leave", "katib:step"),
        ("enter", "katib:report"), ("enter", "katib:store_write"), ("leave", "katib:store_write"),
        ("leave", "katib:report"),
        ("leave", "katib:steps"),
    ]
    seen.clear()
    with tracer.span("anything", "e"):
        pass
    assert seen == [("enter", "katib:anything"), ("leave", "katib:anything")]
    # spans of the explicit, cross-thread API are not mirrored
    seen.clear()
    tracer.end_span(tracer.start_span("queue_wait", "e", "a" * 32))
    assert seen == []


def test_the_mirror_and_the_ledger_import_no_jax():
    code = (
        "import sys\n"
        "from katib_tpu import tracing\n"
        "from katib_tpu.runtime.context import TrialContext\n"
        "class R:\n"
        "    store = None\n"
        "    def report(self, **m): pass\n"
        "tracer = tracing.Tracer(enabled=True)\n"
        "ctx = TrialContext('t', 'e', {}, R())\n"
        "root = tracer.begin_trial('e', 't')\n"
        "ctx.bind_trace(tracer, 'e', root.trace_id, root.span_id)\n"
        "ctx._trace_fn_start()\n"
        "step = ctx.watch_step(lambda: 1)\n"
        "with ctx.span('build'): pass\n"
        "for _ in range(3):\n"
        "    step(); ctx.report(loss=1.0)\n"
        "ctx._trace_fn_end()\n"
        "with tracer.span('x', 'e'): pass\n"
        "names = [s.name for s in tracer.trace_spans('e', root.trace_id)]\n"
        "assert names == ['trial', 'compile', 'build', 'steps'], names\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
        "assert not tracing._compile_listener_installed and not tracing._compile_routes\n"
    )
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True,
                          timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]


# ---------------------------------------------------------------------------
# through the controller: a killed trial, and tracing switched off
# ---------------------------------------------------------------------------

def _long_trial(started):
    def fn(assignments, ctx):
        step = ctx.watch_step(lambda: time.sleep(0.001))
        fn.watched_is_identity = step.__name__ == "<lambda>"
        fn.tracer = ctx.tracer
        while True:                       # until the controller kills it, at a report
            for _ in range(5):
                step()
            ctx.report(score=0.5)
            started.set()

    return fn


def test_a_killed_in_process_trial_still_persists_its_steps_span(tmp_path):
    started = threading.Event()
    ctrl = ExperimentController(root_dir=str(tmp_path), devices=list(range(1)))
    ctrl.create_experiment(make_spec("killed", fn=_long_trial(started)))
    runner = threading.Thread(target=lambda: ctrl.run("killed", timeout=60), daemon=True)
    runner.start()
    assert started.wait(30)
    time.sleep(0.2)
    ctrl.close()
    runner.join(timeout=30)
    folder = tmp_path / "traces" / "killed"
    (path,) = list(folder.iterdir())
    with open(path) as f:
        spans = {s["name"]: s for s in json.load(f)["spans"]}
    steps = spans["steps"]
    assert steps["end"] is not None and spans["trial"]["attrs"]["outcome"] == "Killed"
    attrs = steps["attrs"]
    assert attrs["reports"] == len(attrs["intervals"]) + 1 >= 2
    assert attrs["steps"] == 5 * len(attrs["intervals"])
    assert attrs["seconds"] == pytest.approx(steps["end"] - steps["start"], abs=0.05)
    assert attrs["dispatch_s"] >= 0.001 * attrs["steps"]


@pytest.fixture
def untraced_run(tmp_path, monkeypatch):
    """One trial through a controller that was built with KATIB_TPU_TRACING=0,
    with the process's compile listener taken off first if an earlier test
    put it on."""
    import jax
    from jax._src import monitoring

    monkeypatch.setenv("KATIB_TPU_TRACING", "0")
    was_installed = tracing._compile_listener_installed
    if was_installed:
        jax.monitoring.unregister_event_time_span_listener(tracing._on_compile_time_span)
        jax.monitoring.unregister_event_listener(tracing._on_compile_event)
    monkeypatch.setattr(tracing, "_compile_listener_installed", False)
    notes = []
    real = tracing.enter_annotation
    monkeypatch.setattr(tracing, "enter_annotation", lambda name: notes.append(name) or real(name))
    started = threading.Event()
    fn = _long_trial(started)
    ctrl = ExperimentController(root_dir=str(tmp_path), devices=list(range(1)))
    ctrl.create_experiment(make_spec("untraced", fn=fn))
    runner = threading.Thread(target=lambda: ctrl.run("untraced", timeout=60), daemon=True)
    runner.start()
    assert started.wait(30)
    ctrl.close()
    runner.join(timeout=30)
    yield fn, notes, monitoring
    if was_installed:
        jax.monitoring.register_event_time_span_listener(tracing._on_compile_time_span)
        jax.monitoring.register_event_listener(tracing._on_compile_event)
        tracing._compile_listener_installed = True


def test_with_tracing_off_no_listener_is_registered(untraced_run):
    _, _, monitoring = untraced_run
    assert not tracing._compile_listener_installed
    assert tracing._on_compile_time_span not in monitoring.get_event_time_span_listeners()
    assert tracing._on_compile_event not in monitoring.get_event_listeners()
    assert not tracing._compile_routes


def test_with_tracing_off_watch_step_is_the_identity(untraced_run):
    fn, _, _ = untraced_run
    assert fn.tracer is None and fn.watched_is_identity


def test_with_tracing_off_no_katib_annotation_is_made(untraced_run, tmp_path):
    _, notes, _ = untraced_run
    assert notes == []
    assert not (tmp_path / "traces").exists() or not list((tmp_path / "traces").iterdir())
