"""End-to-end trial lifecycle tracing — spans, context propagation, export.

The reference's observability ceiling is logs plus counter/gauge Prometheus
metrics (SURVEY.md §5, prometheus_metrics.go); after vmapped packing (PR 1),
preemptive fair-share (PR 2) and the buffered obslog (PR 3) multiplied
concurrency, "where did this trial's wall-clock go?" is unanswerable from
those surfaces. Podracer-style TPU stacks (arXiv:2104.06272) live and die by
per-stage timing; this module supplies it:

- :class:`Span` — ``{trace_id, span_id, parent_id, name, start, end, attrs}``
  records collected into a bounded, thread-safe per-experiment ring;
- :class:`Tracer` — one trace per trial (root span ``trial`` from submission
  to terminal condition) with child spans for every lifecycle stage:
  suggestion, admission, queue wait, pack formation, dispatch/run, executor
  setup, first-step compile vs steady-state steps, checkpoint save/restore,
  obslog flush barriers, preemption and finalization. Packed trials get one
  gang-level trace whose root ``pack`` span has K ``member:*`` child spans;
- W3C-traceparent-style context (``00-<trace>-<span>-01``) propagated to
  subprocess trials via ``KATIB_TPU_TRACEPARENT`` and rejoined on the
  ``report_metrics`` env binding and the ReportObservationLog RPC;
- span ends feed the ``katib_span_duration_seconds{stage=...}`` histogram in
  the MetricsRegistry (controller/events.py);
- exports: span-tree text rendering (``katib-tpu trace``), Chrome/Perfetto
  ``trace_event`` JSON (``GET .../trace?format=perfetto``, openable in
  ui.perfetto.dev alongside the xplane dumps), and per-trial JSON
  persistence under ``<root>/traces/`` so traces outlive the controller.

Disabled (``runtime.tracing=false`` / ``KATIB_TPU_TRACING=0``) the tracer
costs one boolean check per call site: ``span()`` hands back a shared no-op
context manager and every ``begin_*``/``start_span`` returns None.
"""

from __future__ import annotations

import collections
import contextlib
import json
import logging
import os
import re
import sys
import threading
import time
import uuid
from contextvars import ContextVar
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

ENV_TRACING = "KATIB_TPU_TRACING"
ENV_TRACEPARENT = "KATIB_TPU_TRACEPARENT"
ENV_WIRE_TRACING = "KATIB_TPU_WIRE_TRACING"

SPAN_DURATION_METRIC = "katib_span_duration_seconds"

_TRACEPARENT_RE = re.compile(r"^00-([0-9a-f]{32})-([0-9a-f]{16})-[0-9a-f]{2}$")


def tracing_enabled_from_env(default: bool = True) -> bool:
    raw = os.environ.get(ENV_TRACING)
    if raw is None or raw == "":
        return default
    return raw.lower() not in ("0", "false", "off")


def wire_tracing_from_env(default: bool = False) -> bool:
    """Client-side resolution of the wire-tracing knob (ISSUE 19): trial
    subprocesses and wire clients have no RuntimeConfig handle, so the env
    override IS the knob for them. Default off = byte-identical wire."""
    raw = os.environ.get(ENV_WIRE_TRACING)
    if raw is None or raw == "":
        return default
    return raw.lower() not in ("0", "false", "off")


def format_traceparent(trace_id: str, span_id: str) -> str:
    """W3C trace-context shape (version 00, sampled flag)."""
    return f"00-{trace_id}-{span_id}-01"


def parse_traceparent(value: Optional[str]) -> Optional[Tuple[str, str]]:
    """(trace_id, span_id) or None for a missing/malformed header."""
    if not value:
        return None
    m = _TRACEPARENT_RE.match(value.strip().lower())
    if m is None:
        return None
    return m.group(1), m.group(2)


@dataclass
class Span:
    trace_id: str
    span_id: str
    parent_id: Optional[str]
    name: str
    start: float
    end: Optional[float] = None
    attrs: Dict[str, Any] = field(default_factory=dict)

    @property
    def duration(self) -> float:
        return max((self.end if self.end is not None else time.time()) - self.start, 0.0)

    @property
    def ended(self) -> bool:
        return self.end is not None

    def set(self, **attrs) -> None:
        """Attach attributes mid-span (same surface as the disabled-mode
        no-op span, so call sites never branch)."""
        self.attrs.update(attrs)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "traceId": self.trace_id,
            "spanId": self.span_id,
            "parentId": self.parent_id,
            "name": self.name,
            "start": self.start,
            "end": self.end,
            "durationSeconds": round(self.duration, 6) if self.end is not None else None,
            "attrs": dict(self.attrs),
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Span":
        return cls(
            trace_id=d.get("traceId", ""),
            span_id=d.get("spanId", ""),
            parent_id=d.get("parentId"),
            name=d.get("name", ""),
            start=float(d.get("start", 0.0)),
            end=None if d.get("end") is None else float(d["end"]),
            attrs=dict(d.get("attrs") or {}),
        )


class _NoopSpan:
    """Shared stand-in when tracing is disabled: every method is a no-op, so
    instrumented code never branches beyond the enabled check."""

    __slots__ = ()
    trace_id = ""
    span_id = ""

    def set(self, **attrs) -> None:
        pass


_NOOP_SPAN = _NoopSpan()


class _NoopSpanCM:
    __slots__ = ()

    def __enter__(self):
        return _NOOP_SPAN

    def __exit__(self, *exc):
        return False


_NOOP_CM = _NoopSpanCM()


# current span for the context-manager API (same-thread nesting; the
# scheduler's cross-thread lifecycle spans use explicit parent ids instead)
_current_span: ContextVar[Optional[Span]] = ContextVar("katib_tpu_span", default=None)


def current_traceparent() -> Optional[str]:
    """Propagatable context: the current in-thread span if any, else the
    inherited subprocess context from $KATIB_TPU_TRACEPARENT."""
    span = _current_span.get()
    if span is not None:
        return format_traceparent(span.trace_id, span.span_id)
    tp = os.environ.get(ENV_TRACEPARENT)
    return tp if parse_traceparent(tp) else None


@dataclass
class GangTrace:
    """Handle for one pack's shared trace: root ``pack`` span plus one open
    ``member:<trial>`` child span per member (ended as members finish)."""

    trace_id: str
    root: Span
    members: Dict[str, Span]


class Tracer:
    """Bounded, thread-safe span collector with per-trial trace bookkeeping.

    One ring (deque) of spans per experiment bounds memory; completed trial
    traces are optionally persisted as one small JSON file each under
    ``persist_dir`` so ``katib-tpu trace`` works after the controller exits.
    """

    MAX_TRIAL_INDEX = 8192  # trial -> trace_id mapping bound

    def __init__(
        self,
        enabled: bool = True,
        metrics=None,
        ring_size: int = 4096,
        persist_dir: Optional[str] = None,
    ):
        self.enabled = enabled
        self.metrics = metrics
        self.ring_size = ring_size
        self.persist_dir = persist_dir
        self._lock = threading.Lock()
        self._rings: Dict[str, Deque[Span]] = {}
        # (experiment, trial) -> trace_id, insertion-ordered for the bound
        self._trial_traces: "collections.OrderedDict[Tuple[str, str], str]" = (
            collections.OrderedDict()
        )
        self._roots: Dict[str, Span] = {}  # trace_id -> root span
        # distributed plane (ISSUE 19): a WireSpanSink appends every ended
        # span durably under the SHARED root so cross-replica trees merge
        # even after this process is SIGKILLed; per-experiment annotations
        # (the failover fence token) stamp onto every later span
        self.wire_sink: Optional["WireSpanSink"] = None
        self._annotations: Dict[str, Dict[str, Any]] = {}

    def attach_wire_sink(self, sink: Optional["WireSpanSink"]) -> None:
        self.wire_sink = sink

    def annotate(self, experiment: str, **attrs: Any) -> None:
        """Merge default attrs into every span recorded for ``experiment``
        from now on — the placement failover path stamps the bumped fence
        token here so a taken-over experiment's resumed spans carry it."""
        with self._lock:
            self._annotations.setdefault(experiment, {}).update(attrs)

    # -- id + record plumbing ------------------------------------------------

    @staticmethod
    def new_trace_id() -> str:
        return uuid.uuid4().hex  # 32 hex chars — W3C trace-id width

    @staticmethod
    def new_span_id() -> str:
        return uuid.uuid4().hex[:16]  # 16 hex chars — W3C span-id width

    def _record(self, experiment: str, span: Span) -> None:
        with self._lock:
            defaults = self._annotations.get(experiment)
            ring = self._rings.get(experiment)
            if ring is None:
                ring = self._rings[experiment] = collections.deque(maxlen=self.ring_size)
            ring.append(span)
        if defaults:
            for k, v in defaults.items():
                span.attrs.setdefault(k, v)
        sink = self.wire_sink
        if sink is not None:
            span._wire_experiment = experiment  # type: ignore[attr-defined]
            if span.parent_id is None:
                # root spans are written once at open too, so a SIGKILL
                # mid-trial still leaves the victim's trace anchored
                sink.record(span, experiment)

    # -- explicit span API (cross-thread lifecycle instrumentation) ----------

    def start_span(
        self,
        name: str,
        experiment: str,
        trace_id: str,
        parent_id: Optional[str] = None,
        start: Optional[float] = None,
        attrs: Optional[Dict[str, Any]] = None,
    ) -> Optional[Span]:
        if not self.enabled:
            return None
        span = Span(
            trace_id=trace_id,
            span_id=self.new_span_id(),
            parent_id=parent_id,
            name=name,
            start=time.time() if start is None else start,
            attrs=dict(attrs or {}),
        )
        self._record(experiment, span)
        return span

    def end_span(self, span: Optional[Span], end: Optional[float] = None, **attrs) -> None:
        if span is None or span.end is not None:
            return
        if attrs:
            span.attrs.update(attrs)
        span.end = time.time() if end is None else end
        if self.metrics is not None:
            try:
                self.metrics.observe(SPAN_DURATION_METRIC, span.duration, stage=span.name)
            except Exception:
                pass  # a histogram bug must never unwind the traced path
        sink = self.wire_sink
        if sink is not None:
            sink.record(span, getattr(span, "_wire_experiment", ""))

    def record_span(
        self,
        name: str,
        experiment: str,
        trace_id: str,
        parent_id: Optional[str],
        start: float,
        end: float,
        **attrs,
    ) -> Optional[Span]:
        """Record an already-measured interval (e.g. the suggestion batch
        window stamped onto every trial of the batch)."""
        span = self.start_span(
            name, experiment, trace_id, parent_id, start=start, attrs=attrs
        )
        if span is not None:
            self.end_span(span, end=end)
        return span

    # -- context-manager API (same-thread nesting) ---------------------------

    def span(
        self,
        name: str,
        experiment: str = "",
        trace_id: Optional[str] = None,
        parent_id: Optional[str] = None,
        **attrs,
    ):
        """``with tracer.span("stage", attr=...)``: nests under the current
        in-thread span (or under the subprocess-inherited traceparent) unless
        trace_id/parent_id pin the context explicitly. Near-zero overhead
        when disabled: a shared no-op context manager is returned."""
        if not self.enabled:
            return _NOOP_CM
        return _SpanCM(self, name, experiment, trace_id, parent_id, attrs)

    # -- trial lifecycle -----------------------------------------------------

    def begin_trial(
        self, experiment: str, trial: str, start: Optional[float] = None, **attrs
    ) -> Optional[Span]:
        """Open (or return the still-open) root span of the trial's trace."""
        if not self.enabled:
            return None
        with self._lock:
            trace_id = self._trial_traces.get((experiment, trial))
            root = self._roots.get(trace_id) if trace_id else None
        if root is not None and root.end is None:
            return root  # resubmit of an in-flight trace (resume path)
        sink = self.wire_sink
        if root is None and sink is not None:
            adopted = sink.adopt_trial_root(experiment, trial)
            if adopted is not None:
                # failover resume (ISSUE 19): rejoin the dead replica's
                # still-open trace so victim + takeover spans merge into ONE
                # cross-replica tree; per-experiment annotations (the bumped
                # fence token) stamp onto the adopted root via _record
                adopted.attrs.update(attrs)
                self._record(experiment, adopted)
                with self._lock:
                    self._trial_traces[(experiment, trial)] = adopted.trace_id
                    self._trial_traces.move_to_end((experiment, trial))
                    while len(self._trial_traces) > self.MAX_TRIAL_INDEX:
                        _, old_trace = self._trial_traces.popitem(last=False)
                        self._roots.pop(old_trace, None)
                    self._roots[adopted.trace_id] = adopted
                return adopted
        trace_id = self.new_trace_id()
        root = Span(
            trace_id=trace_id,
            span_id=self.new_span_id(),
            parent_id=None,
            name="trial",
            start=time.time() if start is None else start,
            attrs={"experiment": experiment, "trial": trial, **attrs},
        )
        self._record(experiment, root)
        with self._lock:
            self._trial_traces[(experiment, trial)] = trace_id
            self._trial_traces.move_to_end((experiment, trial))
            while len(self._trial_traces) > self.MAX_TRIAL_INDEX:
                _, old_trace = self._trial_traces.popitem(last=False)
                self._roots.pop(old_trace, None)
            self._roots[trace_id] = root
        return root

    def trial_root(self, experiment: str, trial: str) -> Optional[Span]:
        if not self.enabled:
            return None
        with self._lock:
            trace_id = self._trial_traces.get((experiment, trial))
            return self._roots.get(trace_id) if trace_id else None

    def end_trial(self, experiment: str, trial: str, **attrs) -> None:
        """End the trial's root span (idempotent) and persist the trace."""
        root = self.trial_root(experiment, trial)
        if root is None or root.end is not None:
            return
        self.end_span(root, **attrs)
        self._persist(experiment, trial, root.trace_id)

    def begin_gang(
        self, experiment: str, pack_id: str, trials: Sequence[str]
    ) -> Optional[GangTrace]:
        """One gang-level trace per pack: root ``pack`` span with K open
        ``member:<trial>`` children, each linked to the member's own trial
        trace via the ``trialTraceId`` attr."""
        if not self.enabled:
            return None
        trace_id = self.new_trace_id()
        root = Span(
            trace_id=trace_id,
            span_id=self.new_span_id(),
            parent_id=None,
            name="pack",
            start=time.time(),
            attrs={"experiment": experiment, "pack": pack_id, "members": len(trials)},
        )
        self._record(experiment, root)
        members: Dict[str, Span] = {}
        for name in trials:
            trial_root = self.trial_root(experiment, name)
            m = Span(
                trace_id=trace_id,
                span_id=self.new_span_id(),
                parent_id=root.span_id,
                name=f"member:{name}",
                start=root.start,
                attrs={
                    "trial": name,
                    "trialTraceId": trial_root.trace_id if trial_root else None,
                },
            )
            self._record(experiment, m)
            members[name] = m
        return GangTrace(trace_id=trace_id, root=root, members=members)

    # -- queries / export ----------------------------------------------------

    def trace_spans(self, experiment: str, trace_id: str) -> List[Span]:
        with self._lock:
            ring = self._rings.get(experiment, ())
            return [s for s in ring if s.trace_id == trace_id]

    def trial_trace(self, experiment: str, trial: str) -> Optional[Dict[str, Any]]:
        """``{"traceId", "experiment", "trial", "spans": [...]}`` from the
        live ring, falling back to the persisted file; None when unknown."""
        with self._lock:
            trace_id = self._trial_traces.get((experiment, trial))
        if trace_id:
            spans = self.trace_spans(experiment, trace_id)
            if spans:
                return {
                    "traceId": trace_id,
                    "experiment": experiment,
                    "trial": trial,
                    "spans": [s.to_dict() for s in spans],
                }
        return self._load_persisted(experiment, trial)

    def forget(self, experiment: str) -> None:
        with self._lock:
            self._rings.pop(experiment, None)
            for key in [k for k in self._trial_traces if k[0] == experiment]:
                self._roots.pop(self._trial_traces.pop(key), None)

    # -- persistence ---------------------------------------------------------

    def _trace_path(self, experiment: str, trial: str) -> Optional[str]:
        if not self.persist_dir:
            return None
        bad = any(
            "/" in n or "\\" in n or ".." in n or "\x00" in n or not n
            for n in (experiment, trial)
        )
        if bad:
            return None
        return os.path.join(self.persist_dir, experiment, f"{trial}.json")

    def _persist(self, experiment: str, trial: str, trace_id: str) -> None:
        path = self._trace_path(experiment, trial)
        if path is None:
            return
        payload = {
            "traceId": trace_id,
            "experiment": experiment,
            "trial": trial,
            "spans": [s.to_dict() for s in self.trace_spans(experiment, trace_id)],
        }
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(payload, f)
            os.replace(tmp, path)
        except OSError:
            logging.getLogger("katib_tpu.tracing").warning(
                "failed to persist trace for %s/%s", experiment, trial, exc_info=True
            )

    def _load_persisted(self, experiment: str, trial: str) -> Optional[Dict[str, Any]]:
        path = self._trace_path(experiment, trial)
        if path is None or not os.path.exists(path):
            return None
        try:
            with open(path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None


class _SpanCM:
    """Context manager returned by Tracer.span when enabled."""

    __slots__ = ("_tracer", "_name", "_experiment", "_trace_id", "_parent_id", "_attrs", "_stage", "_token")

    def __init__(self, tracer, name, experiment, trace_id, parent_id, attrs):
        self._tracer = tracer
        self._name = name
        self._experiment = experiment
        self._trace_id = trace_id
        self._parent_id = parent_id
        self._attrs = attrs
        self._stage = None
        self._token = None

    def __enter__(self) -> Span:
        trace_id, parent_id = self._trace_id, self._parent_id
        if trace_id is None:
            parent = _current_span.get()
            if parent is not None:
                trace_id, parent_id = parent.trace_id, parent.span_id
            else:
                inherited = parse_traceparent(os.environ.get(ENV_TRACEPARENT))
                if inherited is not None:
                    trace_id, parent_id = inherited
                else:
                    trace_id = Tracer.new_trace_id()
        self._stage = StageSpan(
            self._tracer, self._name, self._experiment, trace_id, parent_id, self._attrs
        )
        self._token = _current_span.set(self._stage.span)
        return self._stage.span

    def __exit__(self, exc_type, exc, tb):
        _current_span.reset(self._token)
        self._stage.end(**({"error": exc_type.__name__} if exc_type else {}))
        return False


# -- both clocks: the profiler's mirror of one-thread spans -------------------
#
# A span that one thread opens and closes is also a
# ``jax.profiler.TraceAnnotation`` named ``katib:<name>``, so any xplane
# trace of the process (``ctx.profile()``, a harness's) shows the program's
# spans on the profiler's clock beside the device's operations. Only where
# ``jax`` is already imported (the CLI and controllers of subprocess trials
# never import it), and only from call sites that tracing being on guards.
# With no profiler session an annotation is a flag check.

ANNOTATION_PREFIX = "katib:"


def enter_annotation(name: str):
    """An entered ``katib:<name>`` annotation, or None where this process has
    not imported jax. Leave it on the same thread with leave_annotation."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        note = jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name)
    except AttributeError:  # another thread is half way through importing jax
        return None
    note.__enter__()
    return note


def leave_annotation(note) -> None:
    if note is not None:
        note.__exit__(None, None, None)


class StageSpan:
    """A span that one thread opens and closes, on both clocks: what
    ``Tracer.span`` blocks are made of, and by itself the runtime's ``compile``
    and ``steps``, which do not nest lexically."""

    __slots__ = ("span", "_tracer", "_note")

    def __init__(self, tracer: Tracer, name: str, experiment: str, trace_id: str,
                 parent_id: Optional[str], attrs: Optional[Dict[str, Any]] = None):
        self._tracer = tracer
        self.span = tracer.start_span(name, experiment, trace_id, parent_id, attrs=attrs)
        self._note = enter_annotation(name) if self.span is not None else None

    def end(self, **attrs) -> None:
        leave_annotation(self._note)
        self._note = None
        self._tracer.end_span(self.span, **attrs)


# -- step ledger ---------------------------------------------------------------

class StepLedger:
    """Where a trial thread's time goes between two reports.

    Each report closes one *interval*, from the previous report's exit to its
    own: the seconds inside step calls (``dispatch_s``), from the last step
    call's return to the report's entry (``wait_s``: the trial's
    ``float(loss)``, the thread waiting for the device), inside the report
    (``report_s``) and, of those, inside the store write (``store_s``). What is
    left of ``seconds`` is the trial function's own Python. Durations are on
    ``clock`` (``time.perf_counter``), ``t_end`` on ``wall`` (``time.time``,
    the Tracer's clock). Steps called before the first report belong to the
    ``compile`` span: the first report hands them over (``first_report``) and
    starts the first interval at its exit.

    A trial may add counters of its own to the open interval (``count``: a
    routed layer's loads, say): an interval's row then carries their sums
    after the fixed fields, under their names, in the order they first came.

    One thread writes it; the newest ``RING`` intervals are kept, the totals
    keep counting.
    """

    RING = 1024
    FIELDS = ("t_end", "seconds", "steps", "dispatch_s", "wait_s", "report_s", "store_s")

    def __init__(self, clock=time.perf_counter, wall=time.time):
        self.clock = clock
        self.wall = wall
        self.intervals: Deque[Tuple[float, ...]] = collections.deque(maxlen=self.RING)
        self.totals: Dict[str, float] = dict.fromkeys(self.FIELDS[1:], 0)
        self._t_prev: Optional[float] = None  # the previous report's exit
        self._steps = 0
        self._dispatch = 0.0
        self._last_return: Optional[float] = None
        self._store = 0.0
        self._counted: Dict[str, float] = {}  # the trial's own counters; the keys stay, in order

    def count(self, counters: Dict[str, float]) -> None:
        """Add to the open interval's counters."""
        for name, value in counters.items():
            self._counted[name] = self._counted.get(name, 0.0) + value
            self.totals.setdefault(name, 0.0)

    def stepped(self, t_call: float, t_return: float) -> None:
        """One step call returned (``clock`` readings around it)."""
        self._steps += 1
        self._dispatch += t_return - t_call
        self._last_return = t_return

    def stored(self, seconds: float) -> None:
        """The store write inside the report that is open."""
        self._store += seconds

    def _wait(self, t_entry: float) -> float:
        return t_entry - self._last_return if self._last_return is not None else 0.0

    def first_report(self, t_entry: float) -> Dict[str, float]:
        """The step calls so far, for the ``compile`` span that this report ends."""
        return {"steps": self._steps, "dispatch_s": self._dispatch, "wait_s": self._wait(t_entry)}

    def reported(self, t_entry: float) -> None:
        """A report that was entered at ``t_entry`` is leaving now."""
        now = self.clock()
        if self._t_prev is not None:
            row = (
                self.wall(), now - self._t_prev, self._steps, self._dispatch,
                self._wait(t_entry), now - t_entry, self._store,
            ) + tuple(self._counted.values())
            self.intervals.append(row)
            for key, value in zip(self.FIELDS[1:] + tuple(self._counted), row[1:]):
                self.totals[key] += value
        self._t_prev = now
        self._steps, self._dispatch, self._last_return, self._store = 0, 0.0, None, 0.0
        self._counted = dict.fromkeys(self._counted, 0.0)

    def attrs(self) -> Dict[str, Any]:
        """What the ``steps`` span carries when it ends."""
        return dict(
            self.totals, interval_fields=list(self.FIELDS) + list(self._counted),
            intervals=[list(row) for row in self.intervals],
        )


# -- compile stages: jax.monitoring's timed events, routed by thread -----------

COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}


class _CompileRoute:
    """What JAX timed on one thread while its ``compile`` span is open. JAX
    times every jitted function it traces, the ones inside another's trace
    too, so of one stage only the outermost events are kept: the children
    of one stage never overlap, and their seconds add up. Events under
    ``SHORT_S`` (a hundred or more of them in a model's initialisation) are
    only counted, ``{stage: [events, seconds]}``."""

    SHORT_S = 1e-3
    __slots__ = ("events", "short", "cache")

    def __init__(self) -> None:
        self.events: List[Tuple[str, float, float, Dict[str, Any]]] = []
        self.short: Dict[str, List[float]] = {}
        self.cache: Optional[str] = None  # a verdict no backend_compile carries yet

    def add(self, name: str, start: float, end: float) -> None:
        if end - start < self.SHORT_S:
            count = self.short.setdefault(name, [0, 0.0])
            count[0] += 1
            count[1] += end - start
            return
        attrs = {}
        if name == "backend_compile" and self.cache is not None:
            attrs["cache"], self.cache = self.cache, None
        # an event ends after everything inside it: what it encloses is here already
        self.events = [e for e in self.events if e[0] != name or e[1] < start]
        self.events.append((name, start, end, attrs))


# thread ident -> its route; a thread adds and removes only its own
_compile_routes: Dict[int, _CompileRoute] = {}
_compile_listener_lock = threading.Lock()
_compile_listener_installed = False


def _on_compile_time_span(event: str, start: float, end: float, **_kw) -> None:
    name = COMPILE_STAGES.get(event)
    route = _compile_routes.get(threading.get_ident()) if name else None
    if route is not None:
        route.add(name, start, end)  # JAX's own time.time() readings: the Tracer's clock


def _on_compile_event(event: str, **_kw) -> None:
    verdict = _CACHE_EVENTS.get(event)
    route = _compile_routes.get(threading.get_ident()) if verdict else None
    if route is not None:
        route.cache = verdict


def route_compile_events() -> None:
    """From now until unroute_compile_events, collect each trace, lowering and
    backend compile that JAX times on the calling thread. The process's one
    listener is registered at the first call; nothing happens where jax is
    not imported."""
    global _compile_listener_installed
    jax = sys.modules.get("jax")
    if jax is None:
        return
    with _compile_listener_lock:
        if not _compile_listener_installed:
            try:
                jax.monitoring.register_event_time_span_listener(_on_compile_time_span)
                jax.monitoring.register_event_listener(_on_compile_event)
            except AttributeError:  # half-imported jax, or one without these hooks
                return
            _compile_listener_installed = True
    _compile_routes[threading.get_ident()] = _CompileRoute()


def unroute_compile_events(
    tracer: Tracer, experiment: str, trace_id: str, parent_id: str
) -> Dict[str, List[float]]:
    """Stop collecting on the calling thread and record what was collected as
    child spans of ``parent_id``: ``jaxpr_trace``, ``lower`` and
    ``backend_compile`` (with the persistent cache's ``hit`` or ``miss``
    where JAX reported one). Returns the count of the events too short for a
    span of their own, for the parent's attrs."""
    route = _compile_routes.pop(threading.get_ident(), None)
    if route is None:
        return {}
    for name, start, end, attrs in route.events:
        tracer.record_span(name, experiment, trace_id, parent_id, start, end, **attrs)
    return route.short


# -- process-global tracer (subprocess trials, RPC services) -----------------

_default_tracer: Optional[Tracer] = None
_default_lock = threading.Lock()


def default_tracer() -> Tracer:
    """Lazily-created process tracer for code with no controller handle:
    subprocess trials that inherited $KATIB_TPU_TRACEPARENT, and the gRPC
    service side of the ReportObservationLog rejoin."""
    global _default_tracer
    with _default_lock:
        if _default_tracer is None:
            _default_tracer = Tracer(enabled=tracing_enabled_from_env())
        return _default_tracer


def record_env_report(n_metrics: int) -> Optional[Span]:
    """Rejoin point for the report_metrics env binding: a subprocess trial's
    push lands a ``report_metrics`` span in the child's tracer carrying the
    controller-issued trace/parent ids, so merged views form one tree."""
    ctx = parse_traceparent(os.environ.get(ENV_TRACEPARENT))
    if ctx is None:
        return None
    tracer = default_tracer()
    if not tracer.enabled:
        return None
    trace_id, parent_id = ctx
    experiment = os.environ.get("KATIB_TPU_EXPERIMENT", "") or "_remote"
    span = tracer.start_span(
        "report_metrics", experiment, trace_id, parent_id,
        attrs={"metrics": int(n_metrics)},
    )
    tracer.end_span(span)
    return span


# -- structured logging ------------------------------------------------------

_log_ctx: ContextVar[Optional[Dict[str, str]]] = ContextVar(
    "katib_tpu_log_ctx", default=None
)


def push_log_context(**fields: str):
    """Stamp experiment=/trial=/trace_id= onto subsequent log lines of this
    thread (loggers wired via install_log_context). Returns a token for
    pop_log_context."""
    merged = dict(_log_ctx.get() or {})
    merged.update({k: v for k, v in fields.items() if v})
    return _log_ctx.set(merged)


def pop_log_context(token) -> None:
    _log_ctx.reset(token)


@contextlib.contextmanager
def log_context(**fields: str):
    token = push_log_context(**fields)
    try:
        yield
    finally:
        pop_log_context(token)


class TraceContextFilter(logging.Filter):
    """Appends the ambient trial context to log lines —
    ``... [experiment=e trial=t trace_id=abc]`` — so concurrent trials'
    interleaved controller/runtime logs are attributable."""

    def filter(self, record: logging.LogRecord) -> bool:
        ctx = _log_ctx.get()
        if ctx:
            suffix = " ".join(f"{k}={v}" for k, v in ctx.items())
            record.msg = f"{record.msg} [{suffix}]"
        return True


_installed_loggers: set = set()
_installed_loggers_lock = threading.Lock()

LOGGERS = (
    "katib_tpu.scheduler",
    "katib_tpu.executor",
    "katib_tpu.experiment",
)


def install_log_context(*names: str) -> None:
    """Idempotently wire the context filter into the named loggers (default:
    scheduler + executor + experiment). Locked: two controllers constructed
    concurrently (tests do this) must not double-install a filter through
    the check-then-add race."""
    with _installed_loggers_lock:
        for name in names or LOGGERS:
            if name in _installed_loggers:
                continue
            _installed_loggers.add(name)
            logging.getLogger(name).addFilter(TraceContextFilter())


# -- distributed tracing plane (ISSUE 19) ------------------------------------
#
# When runtime.wire_tracing is on, every ended span is appended as one JSON
# line under the SHARED state root: <root>/traces/wire/<trace_id>/<replica>
# .jsonl. Append-only jsonl is the crash-durability idiom here (a torn last
# line is skipped by the reader; KTI305's tmp+os.replace applies to whole-
# file rewrites, not logs), and the directory key IS the trace id, so a
# cross-replica merge is one readdir — no matter which replica died when.

_TRACE_ID_RE = re.compile(r"^[0-9a-f]{32}$")
_SAFE_COMPONENT_RE = re.compile(r"[^A-Za-z0-9._-]")

WIRE_TRACEPARENT_HEADER = "X-Katib-Traceparent"
# adversarial bound: headers/frame fields longer than this are ignored
# loudly rather than parsed (a valid traceparent is exactly 55 bytes)
MAX_TRACEPARENT_LEN = 128


class WireSpanSink:
    """Durable, replica-tagged span appender on the shared state root.

    One jsonl file per (trace, replica); records carry experiment/trial/
    replica alongside the span so offline merges need no other index. Write
    failures are logged once and never unwind the traced path.
    """

    def __init__(self, root_dir: str, replica: str):
        self.root_dir = root_dir
        self.dir = os.path.join(root_dir, "traces", "wire")
        self.replica = _SAFE_COMPONENT_RE.sub("_", replica or "replica") or "replica"
        self._lock = threading.Lock()
        self._error_logged = False

    def record(self, span: Span, experiment: str = "") -> None:
        if not _TRACE_ID_RE.match(span.trace_id or ""):
            return
        rec = span.to_dict()
        rec["experiment"] = experiment
        rec["trial"] = span.attrs.get("trial", "")
        rec["replica"] = self.replica
        line = json.dumps(rec) + "\n"
        path = os.path.join(self.dir, span.trace_id, f"{self.replica}.jsonl")
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with self._lock, open(path, "a") as f:
                f.write(line)
                f.flush()
        except OSError:
            if not self._error_logged:
                self._error_logged = True
                logging.getLogger("katib_tpu.tracing").warning(
                    "wire span sink write failed under %s (logged once)",
                    self.dir, exc_info=True,
                )
            return
        if (
            span.parent_id is None
            and span.end is None
            and span.name == "trial"
            and span.attrs.get("trial")
        ):
            # trial-root index: one append per begin_trial, sharded per
            # experiment, so a takeover replica can find the victim's
            # still-open trace and REJOIN it instead of forking a new one
            try:
                entry = json.dumps({
                    "trial": span.attrs["trial"],
                    "traceId": span.trace_id,
                    "spanId": span.span_id,
                })
                ipath = self._trial_index_path(experiment)
                os.makedirs(os.path.dirname(ipath), exist_ok=True)
                with self._lock, open(ipath, "a") as f:
                    f.write(entry + "\n")
                    f.flush()
            except OSError:
                pass  # adoption degrades to a fresh trace; spans still merge

    def _trial_index_path(self, experiment: str) -> str:
        safe = _SAFE_COMPONENT_RE.sub("_", experiment or "_") or "_"
        return os.path.join(self.dir, "_trials", safe + ".jsonl")

    def adopt_trial_root(self, experiment: str, trial: str) -> Optional[Span]:
        """The failover-resume rejoin point: the most recent STILL-OPEN root
        span another replica recorded for (experiment, trial), or None when
        the trial was never traced or ended cleanly (a re-run then starts
        its own trace — adopting a finished tree would conflate two runs)."""
        best: Optional[Dict[str, Any]] = None
        try:
            with open(self._trial_index_path(experiment)) as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue  # torn tail line from a SIGKILLed writer
                    if rec.get("trial") == trial and rec.get("traceId"):
                        best = rec  # last wins: the newest begin_trial
        except OSError:
            return None
        if best is None:
            return None
        for rec in load_wire_records(self.root_dir, best["traceId"]):
            if rec.get("spanId") == best.get("spanId"):
                if rec.get("end") is not None:
                    return None  # ended cleanly: nothing to resume
                return Span.from_dict(rec)
        return None


def load_wire_records(root_dir: str, trace_id: str) -> List[Dict[str, Any]]:
    """All replicas' records for one trace, deduped by spanId (an ended
    record supersedes the open root record written at span start)."""
    if not _TRACE_ID_RE.match((trace_id or "").lower()):
        return []
    tdir = os.path.join(root_dir, "traces", "wire", trace_id.lower())
    by_span: Dict[str, Dict[str, Any]] = {}
    try:
        files = sorted(os.listdir(tdir))
    except OSError:
        return []
    for fname in files:
        if not fname.endswith(".jsonl"):
            continue
        try:
            with open(os.path.join(tdir, fname)) as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue  # torn tail line from a SIGKILLed writer
                    sid = rec.get("spanId")
                    if not sid:
                        continue
                    prev = by_span.get(sid)
                    if prev is None or (prev.get("end") is None and rec.get("end") is not None):
                        by_span[sid] = rec
        except OSError:
            continue
    return sorted(by_span.values(), key=lambda r: r.get("start", 0.0))


def merge_trace(root_dir: Optional[str], trace: Optional[Dict[str, Any]],
                trace_id: Optional[str] = None) -> Optional[Dict[str, Any]]:
    """One coherent cross-replica tree: the per-trial persisted/ring trace
    (may be None for a SIGKILLed victim) unioned with every replica's wire
    records for the trace id, deduped by spanId."""
    tid = (trace or {}).get("traceId") or trace_id
    if not tid:
        return trace
    merged: Dict[str, Dict[str, Any]] = {}
    for s in (trace or {}).get("spans", []):
        if s.get("spanId"):
            merged[s["spanId"]] = s
    replicas = set()
    if root_dir:
        for rec in load_wire_records(root_dir, tid):
            if rec.get("replica"):
                replicas.add(rec["replica"])
            prev = merged.get(rec.get("spanId"))
            if prev is None or (prev.get("end") is None and rec.get("end") is not None):
                merged[rec["spanId"]] = rec
    if not merged:
        return trace
    out = dict(trace or {"traceId": tid})
    out["traceId"] = tid
    out["spans"] = sorted(merged.values(), key=lambda s: s.get("start", 0.0))
    if replicas:
        out["replicas"] = sorted(replicas)
    return out


def experiment_traces(root_dir: str, experiment: str) -> List[Dict[str, Any]]:
    """All of one experiment's merged traces, worst-first by root-span
    duration: per-trial persisted traces under ``<root>/traces/<exp>/``
    unioned with wire records, plus wire-only traces (a victim replica's
    trials that never reached end_trial persistence)."""
    traces: List[Dict[str, Any]] = []
    seen_tids: set = set()
    exp_dir = os.path.join(root_dir, "traces", experiment)
    try:
        trial_files = sorted(os.listdir(exp_dir))
    except OSError:
        trial_files = []
    for fname in trial_files:
        if not fname.endswith(".json"):
            continue
        try:
            with open(os.path.join(exp_dir, fname)) as f:
                trace = json.load(f)
        except (OSError, ValueError):
            continue
        merged = merge_trace(root_dir, trace)
        if merged:
            traces.append(merged)
            if merged.get("traceId"):
                seen_tids.add(merged["traceId"])
    # wire-only traces: scan the by-trace dirs and keep those whose records
    # name this experiment (bounded by what the sweep actually wrote)
    wdir = os.path.join(root_dir, "traces", "wire")
    try:
        tids = sorted(os.listdir(wdir))
    except OSError:
        tids = []
    for tid in tids:
        if tid in seen_tids or not _TRACE_ID_RE.match(tid):
            continue
        recs = load_wire_records(root_dir, tid)
        mine = [r for r in recs if r.get("experiment") == experiment]
        if not mine:
            continue
        trials = sorted({r["trial"] for r in mine if r.get("trial")})
        replicas = sorted({r["replica"] for r in recs if r.get("replica")})
        traces.append({
            "traceId": tid,
            "experiment": experiment,
            "trial": trials[0] if len(trials) == 1 else ",".join(trials),
            "spans": recs,
            "replicas": replicas,
        })

    def _root_duration(trace: Dict[str, Any]) -> float:
        spans = [Span.from_dict(s) for s in trace.get("spans", [])]
        roots, _ = build_tree(spans)
        return max((r.duration for r in roots), default=0.0)

    for t in traces:
        t["rootDurationSeconds"] = round(_root_duration(t), 6)
    traces.sort(key=lambda t: t["rootDurationSeconds"], reverse=True)
    return traces


def parse_slo_objectives(spec: str) -> Dict[str, float]:
    """``"default=0.5,CreateExperiment=2.0"`` -> per-method latency
    objectives in seconds; malformed parts are dropped loudly (a typo'd
    objective must not take down the server)."""
    out: Dict[str, float] = {}
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        method, _, raw = part.partition("=")
        try:
            value = float(raw)
        except ValueError:
            logging.getLogger("katib_tpu.tracing").warning(
                "ignoring malformed SLO objective %r (want Method=seconds)", part
            )
            continue
        if method.strip() and value > 0:
            out[method.strip()] = value
    return out


class FlightRecorder:
    """Bounded worst-N slow-RPC ring: each entry keeps the request's method,
    tenant, latency and its span tree, dumpable via GET /api/fleet/slow and
    on SIGUSR2. Admission is by latency — once full, a new request must beat
    the fastest retained entry."""

    def __init__(self, size: int = 32):
        self.size = max(int(size), 0)
        self._lock = threading.Lock()
        self._entries: List[Dict[str, Any]] = []  # sorted slowest-first

    def record(
        self,
        method: str,
        duration: float,
        tenant: str = "",
        trace_id: str = "",
        code: int = 200,
        spans: Optional[List[Dict[str, Any]]] = None,
    ) -> None:
        if self.size <= 0:
            return
        entry = {
            "method": method,
            "tenant": tenant,
            "durationSeconds": round(duration, 6),
            "traceId": trace_id,
            "code": code,
            "time": time.time(),
            "spans": spans or [],
        }
        with self._lock:
            if len(self._entries) >= self.size and duration <= self._entries[-1]["durationSeconds"]:
                return
            self._entries.append(entry)
            self._entries.sort(key=lambda e: e["durationSeconds"], reverse=True)
            del self._entries[self.size:]

    def dump(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(e) for e in self._entries]


# -- export: span tree + Perfetto --------------------------------------------

def build_tree(spans: Sequence[Span]):
    """(roots, children) with children keyed by span_id, both in start
    order; spans whose parent is absent from the set are treated as roots."""
    by_id = {s.span_id: s for s in spans}
    children: Dict[str, List[Span]] = {}
    roots: List[Span] = []
    for s in sorted(spans, key=lambda s: s.start):
        if s.parent_id and s.parent_id in by_id:
            children.setdefault(s.parent_id, []).append(s)
        else:
            roots.append(s)
    return roots, children


def render_tree(spans: Sequence[Span]) -> str:
    """Indented span tree with durations and % of the trial wall-clock —
    the ``katib-tpu trace`` CLI view."""
    if not spans:
        return "(no spans)"
    roots, children = build_tree(spans)
    total = max((r.duration for r in roots), default=0.0) or 1e-9
    width = max(len(s.name) for s in spans) + 2
    lines: List[str] = []

    def _walk(span: Span, depth: int) -> None:
        pct = span.duration / total * 100.0
        label = ("  " * depth + span.name).ljust(width + depth * 2)
        note = "" if span.ended else "  (open)"
        keys = {
            k: f"[{len(v)}]" if isinstance(v, list) else v  # the step ledger's ring: its length
            for k, v in span.attrs.items()
            if k not in ("experiment", "trial") and v not in (None, "")
        }
        attrs = f"  {keys}" if keys else ""
        lines.append(f"{label}{span.duration:>9.3f}s  {pct:>5.1f}%{note}{attrs}")
        for child in children.get(span.span_id, []):
            _walk(child, depth + 1)

    for root in roots:
        _walk(root, 0)
    return "\n".join(lines)


def to_perfetto(spans: Sequence[Span], trace_name: str = "katib-tpu") -> Dict[str, Any]:
    """Chrome ``trace_event`` JSON (the Trace Event Format consumed by
    ui.perfetto.dev and chrome://tracing): complete ``X`` events in
    microseconds, with sibling spans that overlap in time pushed onto
    separate ``tid`` lanes so nesting stays well-formed."""
    now = time.time()
    roots, children = build_tree(spans)
    events: List[Dict[str, Any]] = [
        {
            "name": "process_name",
            "ph": "M",
            "pid": 1,
            "tid": 0,
            "args": {"name": trace_name},
        }
    ]
    lanes: Dict[int, List[Tuple[float, float]]] = {}  # tid -> placed intervals

    def _fits(tid: int, start: float, end: float) -> bool:
        for s0, e0 in lanes.get(tid, ()):
            disjoint = end <= s0 or start >= e0
            contains = s0 <= start and end <= e0
            contained = start <= s0 and e0 <= end
            if not (disjoint or contains or contained):
                return False
        return True

    def _place(span: Span, parent_tid: int) -> None:
        start = span.start
        end = span.end if span.end is not None else now
        tid = parent_tid
        while not _fits(tid, start, end):
            tid += 1
        lanes.setdefault(tid, []).append((start, end))
        events.append(
            {
                "name": span.name,
                "cat": "trial",
                "ph": "X",
                "ts": span.start * 1e6,
                "dur": max(end - start, 0.0) * 1e6,
                "pid": 1,
                "tid": tid,
                "args": {
                    "traceId": span.trace_id,
                    "spanId": span.span_id,
                    **{k: v for k, v in span.attrs.items() if v is not None},
                },
            }
        )
        for child in children.get(span.span_id, []):
            _place(child, tid)

    for root in roots:
        _place(root, 1)
    return {"traceEvents": events, "displayTimeUnit": "ms"}
