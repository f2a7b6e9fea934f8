"""Trial lifecycle tracing (ISSUE 4 tentpole) + metrics-exposition strictness.

Covers:
- span-tree invariants on a completed in-process trial: every span ends,
  parents end after their children, the root covers >=95% of the trial's
  wall-clock, and the expected lifecycle stages are present;
- a preempted-then-resumed trial yields ONE connected trace (two queue
  waits, a `preempted` marker, two runs);
- packed trials share a gang-level trace with K member child spans;
- W3C-traceparent propagation to subprocess trials and the report_metrics /
  RPC rejoin paths;
- Perfetto (Chrome trace_event) export validity and the `katib-tpu trace`
  CLI tree;
- near-zero-overhead disabled mode;
- MetricsRegistry histograms: _bucket/_sum/_count exposition with a STRICT
  line-grammar parse over a live controller's /metrics content (no bare
  `name{}` braces, cumulative bucket monotonicity, _count == +Inf bucket);
- EventRecorder.list_all cross-experiment warning view.
"""

import json
import os
import re
import threading
import time

import pytest

from katib_tpu.api.spec import (
    AlgorithmSpec,
    ExperimentSpec,
    FeasibleSpace,
    ObjectiveSpec,
    ObjectiveType,
    ParameterSpec,
    ParameterType,
    TrialResources,
    TrialTemplate,
)
from katib_tpu.api.status import Experiment, Trial, TrialCondition
from katib_tpu.controller.events import EventRecorder, MetricsRegistry
from katib_tpu.controller.experiment import ExperimentController
from katib_tpu.controller.scheduler import TrialScheduler
from katib_tpu.db.state import ExperimentStateStore
from katib_tpu.db.store import open_store
from katib_tpu.tracing import (
    ENV_TRACEPARENT,
    Span,
    Tracer,
    format_traceparent,
    parse_traceparent,
    render_tree,
    to_perfetto,
)

pytestmark = pytest.mark.smoke


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def make_spec(name, fn=None, command=None, retain=False, pack_size=1, **kw):
    return ExperimentSpec(
        name=name,
        parameters=[
            ParameterSpec("x", ParameterType.DOUBLE, FeasibleSpace(min="0.1", max="1.0"))
        ],
        objective=ObjectiveSpec(
            type=ObjectiveType.MAXIMIZE, objective_metric_name="score"
        ),
        algorithm=AlgorithmSpec("random"),
        trial_template=TrialTemplate(
            function=fn,
            command=command,
            retain=retain,
            resources=TrialResources(pack_size=pack_size),
        ),
        max_trial_count=kw.pop("max_trial_count", 1),
        parallel_trial_count=kw.pop("parallel_trial_count", 1),
        **kw,
    )


def span_index(trace):
    spans = [Span.from_dict(s) for s in trace["spans"]]
    by_id = {s.span_id: s for s in spans}
    return spans, by_id


def assert_tree_invariants(spans, by_id):
    """Every span ends; exactly one root; parents end after children and
    start before them (the connectedness + well-formedness contract)."""
    roots = [s for s in spans if s.parent_id is None or s.parent_id not in by_id]
    assert len(roots) == 1, [s.name for s in roots]
    for s in spans:
        assert s.ended, f"span {s.name} never ended"
        if s.parent_id and s.parent_id in by_id:
            parent = by_id[s.parent_id]
            assert parent.start <= s.start + 1e-6, (parent.name, s.name)
            assert parent.end + 1e-6 >= s.end, (parent.name, s.name)
    trace_ids = {s.trace_id for s in spans}
    assert len(trace_ids) == 1  # one connected trace
    return roots[0]


# ---------------------------------------------------------------------------
# unit: context propagation + disabled mode
# ---------------------------------------------------------------------------

def test_traceparent_roundtrip():
    trace_id, span_id = Tracer.new_trace_id(), Tracer.new_span_id()
    header = format_traceparent(trace_id, span_id)
    assert re.match(r"^00-[0-9a-f]{32}-[0-9a-f]{16}-01$", header)
    assert parse_traceparent(header) == (trace_id, span_id)
    assert parse_traceparent(None) is None
    assert parse_traceparent("") is None
    assert parse_traceparent("garbage") is None
    assert parse_traceparent("00-zz-yy-01") is None


def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    assert tr.begin_trial("e", "t") is None
    assert tr.start_span("s", "e", "abc") is None
    tr.end_span(None)  # tolerated
    with tr.span("anything") as s:
        s.set(foo=1)  # no-op surface
    assert tr.trial_trace("e", "t") is None


def test_span_cm_nests():
    tr = Tracer(enabled=True)
    with tr.span("outer", experiment="e") as outer:
        with tr.span("inner", experiment="e") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
    spans = tr.trace_spans("e", outer.trace_id)
    assert [s.name for s in spans] == ["outer", "inner"]
    assert all(s.ended for s in spans)


def test_span_cm_adopts_subprocess_traceparent(monkeypatch):
    tr = Tracer(enabled=True)
    trace_id, parent = Tracer.new_trace_id(), Tracer.new_span_id()
    monkeypatch.setenv(ENV_TRACEPARENT, format_traceparent(trace_id, parent))
    with tr.span("child_work", experiment="e") as s:
        assert s.trace_id == trace_id
        assert s.parent_id == parent


def test_record_env_report_rejoins(monkeypatch):
    """The report_metrics env-binding rejoin: spans created in a subprocess
    carry the controller-issued trace/parent ids."""
    import katib_tpu.tracing as tracing

    monkeypatch.setattr(tracing, "_default_tracer", None)
    trace_id, parent = Tracer.new_trace_id(), Tracer.new_span_id()
    monkeypatch.setenv(ENV_TRACEPARENT, format_traceparent(trace_id, parent))
    monkeypatch.setenv("KATIB_TPU_EXPERIMENT", "exp-remote")
    span = tracing.record_env_report(3)
    assert span is not None and span.ended
    assert span.trace_id == trace_id and span.parent_id == parent
    assert tracing.default_tracer().trace_spans("exp-remote", trace_id)
    # disabled in the child: no span, no error
    monkeypatch.setenv("KATIB_TPU_TRACING", "0")
    monkeypatch.setattr(tracing, "_default_tracer", None)
    assert tracing.record_env_report(1) is None


def test_ring_bound_and_forget():
    tr = Tracer(enabled=True, ring_size=8)
    for i in range(20):
        s = tr.start_span(f"s{i}", "e", "a" * 32)
        tr.end_span(s)
    assert len(tr.trace_spans("e", "a" * 32)) == 8  # bounded
    tr.begin_trial("e", "t")
    tr.forget("e")
    assert tr.trial_trace("e", "t") is None


# ---------------------------------------------------------------------------
# e2e: solo trial lifecycle trace
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("tracing")

    def fn(assignments, ctx):
        store = ctx.checkpoint_store()
        restored = store.restore()
        start = int(restored["epoch"]) + 1 if restored else 0
        for epoch in range(start, 3):
            store.save(epoch, {"epoch": epoch})
            ctx.report(score=float(epoch) * 0.1)
        ctx.flush_metrics()

    ctrl = ExperimentController(root_dir=str(tmp), devices=list(range(2)))
    ctrl.create_experiment(make_spec("traced", fn=fn, max_trial_count=2,
                                     parallel_trial_count=2))
    exp = ctrl.run("traced", timeout=60)
    yield ctrl, exp, str(tmp)
    ctrl.close()


class TestSoloTrace:
    def test_trace_connected_and_complete(self, traced_run):
        ctrl, exp, _ = traced_run
        assert exp.status.is_succeeded
        trial = ctrl.state.list_trials("traced")[0]
        trace = ctrl.tracer.trial_trace("traced", trial.name)
        assert trace is not None
        spans, by_id = span_index(trace)
        root = assert_tree_invariants(spans, by_id)
        assert root.name == "trial"
        names = {s.name for s in spans}
        # the full lifecycle: suggestion -> admission -> queue -> run ->
        # setup -> execute -> compile/steps -> checkpoint -> flush -> final
        for expected in (
            "suggestion", "admission", "queue_wait", "run", "executor_setup",
            "execute", "compile", "steps", "checkpoint_save",
            "checkpoint_restore", "obslog_flush", "finalize",
        ):
            assert expected in names, f"missing span {expected} in {sorted(names)}"
        assert root.attrs["outcome"] == "Succeeded"

    def test_root_covers_trial_wall_clock(self, traced_run):
        ctrl, _, _ = traced_run
        trial = ctrl.state.list_trials("traced")[0]
        trace = ctrl.tracer.trial_trace("traced", trial.name)
        spans, by_id = span_index(trace)
        root = next(s for s in spans if s.name == "trial")
        first = min(c.last_transition_time for c in trial.conditions)
        last = max(c.last_transition_time for c in trial.conditions)
        wall = max(last - first, 0.0)
        assert root.duration >= 0.95 * wall, (root.duration, wall)

    def test_trace_persisted_to_disk(self, traced_run):
        ctrl, _, root_dir = traced_run
        trial = ctrl.state.list_trials("traced")[0]
        path = os.path.join(root_dir, "traces", "traced", f"{trial.name}.json")
        assert os.path.exists(path)
        with open(path) as f:
            persisted = json.load(f)
        assert persisted["trial"] == trial.name
        assert persisted["spans"]

    def test_perfetto_export_schema(self, traced_run):
        """?format=perfetto output validates against the Chrome trace_event
        shape: a traceEvents list of M/X events with the required keys,
        microsecond timestamps, and well-nested lanes."""
        ctrl, _, _ = traced_run
        trial = ctrl.state.list_trials("traced")[0]
        trace = ctrl.tracer.trial_trace("traced", trial.name)
        spans, _ = span_index(trace)
        doc = to_perfetto(spans)
        assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
        phases = {e["ph"] for e in doc["traceEvents"]}
        assert phases <= {"M", "X"}
        complete = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert len(complete) == len(spans)
        for e in complete:
            for key in ("name", "cat", "ph", "ts", "dur", "pid", "tid", "args"):
                assert key in e, f"{e['name']} missing {key}"
            assert e["dur"] >= 0
            assert isinstance(e["tid"], int)
        # events on one tid lane must be disjoint or properly nested
        by_tid = {}
        for e in complete:
            by_tid.setdefault(e["tid"], []).append((e["ts"], e["ts"] + e["dur"]))
        for intervals in by_tid.values():
            for i, (s0, e0) in enumerate(intervals):
                for s1, e1 in intervals[i + 1:]:
                    disjoint = e0 <= s1 or e1 <= s0
                    nested = (s0 <= s1 and e1 <= e0) or (s1 <= s0 and e0 <= e1)
                    assert disjoint or nested, (intervals,)
        json.dumps(doc)  # must be serializable

    def test_cli_trace_renders_tree(self, traced_run, capsys):
        from katib_tpu.cli import main

        ctrl, _, root_dir = traced_run
        trial = ctrl.state.list_trials("traced")[0]
        rc = main(["--root", root_dir, "trace", "traced", trial.name])
        out = capsys.readouterr().out
        assert rc == 0
        assert "trial" in out and "queue_wait" in out and "compile" in out
        assert "100.0%" in out  # the root line carries the wall-clock share

    def test_cli_trace_missing(self, tmp_path, capsys):
        from katib_tpu.cli import main

        rc = main(["--root", str(tmp_path), "trace", "nope", "missing"])
        assert rc == 1
        assert "no persisted trace" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# e2e: preempted-then-resumed trial — one connected trace
# ---------------------------------------------------------------------------

def _make_exp(name, fn, num_devices=1, priority=""):
    spec = ExperimentSpec(
        name=name,
        parameters=[
            ParameterSpec("x", ParameterType.DOUBLE, FeasibleSpace(min="0", max="1"))
        ],
        objective=ObjectiveSpec(
            type=ObjectiveType.MAXIMIZE, objective_metric_name="score"
        ),
        algorithm=AlgorithmSpec("random"),
        trial_template=TrialTemplate(
            function=fn, resources=TrialResources(num_devices=num_devices)
        ),
        priority_class=priority,
    )
    return Experiment(spec=spec)


def test_preempted_then_resumed_trial_single_trace(tmp_path):
    """The acceptance scenario: a preempted + resumed trial still yields ONE
    connected trace — two queue_wait stints, a `preempted` marker, two runs,
    and a root that spans the whole life."""
    tracer = Tracer(enabled=True)
    state = ExperimentStateStore(None)
    sched = TrialScheduler(
        state,
        open_store(None),
        devices=list(range(8)),
        workdir_root=str(tmp_path / "run"),
        events=EventRecorder(),
        metrics=MetricsRegistry(),
        tracer=tracer,
    )
    gate_reached, gate_go = threading.Event(), threading.Event()

    def victim_fn(assignments, ctx):
        store = ctx.checkpoint_store()
        restored = store.restore()
        start = int(restored["epoch"]) + 1 if restored else 0
        for epoch in range(start, 5):
            store.save(epoch, {"epoch": epoch})
            if epoch == 2 and restored is None:
                gate_reached.set()
                gate_go.wait(timeout=30)
            ctx.report(score=float(epoch))

    def urgent_fn(assignments, ctx):
        ctx.report(score=9.0)

    lo = _make_exp("lo", victim_fn, num_devices=8, priority="low")
    hi = _make_exp("hi", urgent_fn, num_devices=4, priority="high")
    try:
        for exp, tname in ((lo, "victim"), (hi, "urgent")):
            if state.get_experiment(exp.name) is None:
                state.create_experiment(exp)
        trial = Trial(name="victim", experiment_name="lo", parameter_assignments=[])
        state.create_trial(trial)
        sched.submit(lo, trial)
        assert gate_reached.wait(timeout=30)
        t2 = Trial(name="urgent", experiment_name="hi", parameter_assignments=[])
        state.create_trial(t2)
        sched.submit(hi, t2)
        gate_go.set()
        deadline = time.time() + 60
        while time.time() < deadline:
            v = state.get_trial("lo", "victim")
            if v is not None and v.is_terminal:
                break
            time.sleep(0.02)
        v = state.get_trial("lo", "victim")
        assert v.condition == TrialCondition.SUCCEEDED, (v.condition, v.message)
        assert any(c.reason == "TrialPreempted" for c in v.conditions)
    finally:
        gate_go.set()
        sched.kill_all()
        sched.join(timeout=10)

    trace = tracer.trial_trace("lo", "victim")
    assert trace is not None
    spans, by_id = span_index(trace)
    root = assert_tree_invariants(spans, by_id)
    names = [s.name for s in spans]
    assert names.count("queue_wait") == 2  # initial + post-preemption stints
    assert names.count("run") == 2         # preempted run + resumed run
    assert "preempted" in names
    assert "checkpoint_restore" in names   # the resume leg restored
    preempted = next(s for s in spans if s.name == "preempted")
    assert preempted.attrs.get("resumable") is True
    assert root.attrs["outcome"] == "Succeeded"


# ---------------------------------------------------------------------------
# e2e: packed trials share a gang-level trace
# ---------------------------------------------------------------------------

def test_packed_trials_gang_trace():
    from katib_tpu.runtime.packed import population_of, report_population

    def pack_fn(assignments, ctx=None):
        pop = population_of(assignments)
        for step in range(3):
            report_population(ctx, score=pop["x"] * (step + 1))

    pack_fn.supports_packing = True

    ctrl = ExperimentController(root_dir=None, persist=False, devices=list(range(8)))
    try:
        ctrl.create_experiment(
            make_spec("packed", fn=pack_fn, pack_size=4,
                      max_trial_count=4, parallel_trial_count=4)
        )
        exp = ctrl.run("packed", timeout=60)
        assert exp.status.is_succeeded
        trials = ctrl.state.list_trials("packed")
        assert len(trials) == 4
        # every member's own trial trace carries a run span linking to the
        # shared gang trace
        gang_ids = set()
        for t in trials:
            trace = ctrl.tracer.trial_trace("packed", t.name)
            spans, by_id = span_index(trace)
            assert_tree_invariants(spans, by_id)
            run = next(s for s in spans if s.name == "run")
            assert run.attrs.get("packTraceId")
            assert any(s.name == "pack_formation" for s in spans)
            gang_ids.add(run.attrs["packTraceId"])
        assert len(gang_ids) == 1  # one shared program -> one gang trace
        gang_spans = ctrl.tracer.trace_spans("packed", gang_ids.pop())
        gnames = [s.name for s in gang_spans]
        assert "pack" in gnames
        assert sum(1 for n in gnames if n.startswith("member:")) == 4
        assert "compile" in gnames and "steps" in gnames
        assert all(s.ended for s in gang_spans)
    finally:
        ctrl.close()


# ---------------------------------------------------------------------------
# e2e: subprocess trial — traceparent propagation + rejoin
# ---------------------------------------------------------------------------

def test_subprocess_trial_traceparent_rejoins_controller_trace(tmp_path):
    """The executor exports $KATIB_TPU_TRACEPARENT; the child's spans (and
    its report_metrics rejoin) therefore carry the controller's trace id and
    an execute-span parent that exists in the controller trace."""
    import sys

    cmd = [
        sys.executable, "-c",
        "import os; print('tp=' + os.environ.get('KATIB_TPU_TRACEPARENT', 'none')); "
        "print('score=1.0')",
    ]
    ctrl = ExperimentController(root_dir=str(tmp_path), devices=list(range(2)))
    try:
        ctrl.create_experiment(make_spec("subp", command=cmd, retain=True))
        exp = ctrl.run("subp", timeout=60)
        assert exp.status.is_succeeded, exp.status.message
        trial = ctrl.state.list_trials("subp")[0]
        stdout_path = os.path.join(str(tmp_path), "trials", "subp", trial.name, "stdout.log")
        with open(stdout_path) as f:
            content = f.read()
        m = re.search(r"tp=(\S+)", content)
        assert m and m.group(1) != "none", content
        child_trace, child_parent = parse_traceparent(m.group(1))
        trace = ctrl.tracer.trial_trace("subp", trial.name)
        spans, by_id = span_index(trace)
        assert child_trace == trace["traceId"]  # same trace: spans rejoin
        assert child_parent in by_id            # parented on the execute span
        assert by_id[child_parent].name == "execute"
    finally:
        ctrl.close()


# ---------------------------------------------------------------------------
# strict Prometheus exposition grammar over /metrics content
# ---------------------------------------------------------------------------

_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_LABELS = r'\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"(?:,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\}'
_VALUE = r"(?:[+-]?[0-9]+(?:\.[0-9]+)?(?:[eE][+-]?[0-9]+)?|\+Inf|-Inf|NaN)"
SAMPLE_RE = re.compile(rf"^({_NAME})({_LABELS})? {_VALUE}$")
HELP_RE = re.compile(rf"^# HELP ({_NAME}) \S.*$")
TYPE_RE = re.compile(rf"^# TYPE ({_NAME}) (counter|gauge|histogram|summary|untyped)$")
_LABEL_ITEM_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="([^"]*)"')


def _parse_exposition(text):
    """Strict parse of the exposition; returns (types, samples) where
    samples = [(name, {labels}, raw_value_str)]."""
    types, helps, samples = {}, {}, []
    assert text.endswith("\n")
    for line in text.rstrip("\n").splitlines():
        assert "{}" not in line, f"bare-brace series: {line!r}"
        m = HELP_RE.match(line)
        if m:
            assert m.group(1) not in helps, f"duplicate HELP for {m.group(1)}"
            helps[m.group(1)] = line
            continue
        m = TYPE_RE.match(line)
        if m:
            assert m.group(1) not in types, f"duplicate TYPE for {m.group(1)}"
            types[m.group(1)] = m.group(2)
            continue
        m = SAMPLE_RE.match(line)
        assert m, f"line fails the exposition grammar: {line!r}"
        labels = dict(_LABEL_ITEM_RE.findall(m.group(2) or ""))
        samples.append((m.group(1), labels, line.rsplit(" ", 1)[1]))
    return types, helps, samples


def _family(name, types):
    for suffix in ("_bucket", "_sum", "_count"):
        if name.endswith(suffix) and types.get(name[: -len(suffix)]) == "histogram":
            return name[: -len(suffix)]
    return name


def test_metrics_exposition_strict(traced_run):
    """Every /metrics line is HELP, TYPE, or a grammar-valid sample; every
    sample family carries HELP+TYPE; histogram series are internally
    consistent (cumulative monotone buckets, +Inf == _count, _sum present)."""
    ctrl, _, _ = traced_run
    text = ctrl.metrics.render()
    types, helps, samples = _parse_exposition(text)
    hist_buckets, hist_sum, hist_count = {}, set(), {}
    for name, labels, raw in samples:
        family = _family(name, types)
        assert family in types, f"sample {name} has no TYPE"
        assert family in helps, f"sample {name} has no HELP"
        if types[family] == "histogram":
            assert name != family, f"bare histogram sample {name}"
            base = tuple(sorted((k, v) for k, v in labels.items() if k != "le"))
            if name.endswith("_bucket"):
                assert "le" in labels, f"bucket without le: {labels}"
                hist_buckets.setdefault((family, base), []).append(
                    (labels["le"], float(raw))
                )
            elif name.endswith("_sum"):
                hist_sum.add((family, base))
            elif name.endswith("_count"):
                hist_count[(family, base)] = float(raw)
    assert hist_buckets, "no histogram series rendered (tracing produced none?)"
    for key, buckets in hist_buckets.items():
        les = [le for le, _ in buckets]
        assert les[-1] == "+Inf", f"{key} missing +Inf bucket"
        numeric = [float(le) for le in les[:-1]]
        assert numeric == sorted(numeric), f"{key} le bounds not ascending"
        counts = [c for _, c in buckets]
        assert counts == sorted(counts), f"{key} buckets not cumulative-monotone"
        assert key in hist_sum, f"{key} missing _sum"
        assert key in hist_count, f"{key} missing _count"
        assert counts[-1] == hist_count[key], f"{key} +Inf != _count"


def test_render_type_dedup_is_single_per_name():
    """The satellite fix: one # TYPE per name via a seen-set (the old
    expression-statement idiom was an O(n²) list scan)."""
    reg = MetricsRegistry()
    for i in range(50):
        reg.inc("katib_trial_created_total", experiment=f"e{i}")
        reg.set_gauge("katib_queue_depth", float(i), experiment=f"e{i}")
    text = reg.render()
    assert text.count("# TYPE katib_trial_created_total counter") == 1
    assert text.count("# TYPE katib_queue_depth gauge") == 1
    assert text.count("# HELP katib_trial_created_total") == 1


def test_histogram_custom_buckets_and_unlabelled():
    reg = MetricsRegistry()
    reg.observe("my_seconds", 0.3, buckets=(0.1, 1.0))
    reg.observe("my_seconds", 5.0)
    text = reg.render()
    assert 'my_seconds_bucket{le="0.1"} 0.0' in text
    assert 'my_seconds_bucket{le="1"} 1.0' in text
    assert 'my_seconds_bucket{le="+Inf"} 2.0' in text
    assert "my_seconds_sum 5.3" in text
    assert "my_seconds_count 2.0" in text
    _parse_exposition(text)  # grammar holds for unlabelled histograms too


# ---------------------------------------------------------------------------
# EventRecorder cross-experiment view
# ---------------------------------------------------------------------------

def test_event_recorder_list_all_warning_filter():
    rec = EventRecorder()
    rec.event("exp-a", "Trial", "t1", "TrialCreated", "created")
    rec.event("exp-b", "Trial", "t2", "TrialQueueStalled", "stalled", warning=True)
    rec.event("exp-a", "Trial", "t3", "TrialPreempted", "preempted")
    rec.event("exp-c", "Trial", "t4", "ObslogFlushFailed", "boom", warning=True)
    all_events = rec.list_all()
    assert [e.name for e in all_events] == ["t1", "t2", "t3", "t4"]  # time order
    assert {e.experiment for e in all_events} == {"exp-a", "exp-b", "exp-c"}
    warnings = rec.list_all(warning_only=True)
    assert [e.name for e in warnings] == ["t2", "t4"]
    assert rec.list_all(limit=2)[0].name == "t3"
    assert rec.list_all(limit=0) == []
    assert all("experiment" in e.to_dict() for e in all_events)


# ---------------------------------------------------------------------------
# structured logging
# ---------------------------------------------------------------------------

def test_log_context_stamps_scheduler_lines(caplog):
    import logging

    from katib_tpu.tracing import install_log_context, log_context

    install_log_context("katib_tpu.test_logger")
    logger = logging.getLogger("katib_tpu.test_logger")
    with caplog.at_level(logging.INFO, logger="katib_tpu.test_logger"):
        with log_context(experiment="exp-x", trial="t-1", trace_id="abc123"):
            logger.info("trial %s dispatched", "t-1")
        logger.info("outside context")
    stamped = caplog.records[0].getMessage()
    assert "experiment=exp-x" in stamped
    assert "trial=t-1" in stamped and "trace_id=abc123" in stamped
    assert "trial t-1 dispatched" in stamped
    assert "experiment=" not in caplog.records[1].getMessage()


def test_render_tree_shape():
    t0 = 1000.0
    spans = [
        Span("tr" * 16, "a" * 16, None, "trial", t0, t0 + 10.0),
        Span("tr" * 16, "b" * 16, "a" * 16, "queue_wait", t0, t0 + 2.0),
        Span("tr" * 16, "c" * 16, "a" * 16, "run", t0 + 2.0, t0 + 10.0),
    ]
    out = render_tree(spans)
    lines = out.splitlines()
    assert lines[0].startswith("trial")
    assert "100.0%" in lines[0]
    assert lines[1].lstrip().startswith("queue_wait")
    assert "20.0%" in lines[1]
    assert "80.0%" in lines[2]
