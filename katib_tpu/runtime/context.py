"""Trial execution context — what a trial function receives from the runtime.

The TPU-native analogue of everything the reference injects into a trial pod
(env vars, mounted volumes, metrics sidecar wiring, suggestion PVC for PBT —
pkg/webhook/v1beta1/pod/inject_webhook.go): assignments, a push metrics
reporter with early-stopping enforcement, a workdir, the PBT checkpoint dir,
and the gang-allocated device set from which the trial builds its mesh.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from .. import tracing
from .metrics import MetricsReporter


@dataclass
class TrialContext:
    trial_name: str
    experiment_name: str
    assignments: Dict[str, str]
    reporter: MetricsReporter
    workdir: Optional[str] = None
    checkpoint_dir: Optional[str] = None
    devices: Optional[List[Any]] = None  # jax devices gang-allocated to this trial
    labels: Dict[str, str] = field(default_factory=dict)
    topology: Optional[str] = None  # resources.topology — default mesh shape
    # Scheduler hook stamped on every checkpoint save (fairshare victim
    # selection prefers recently-checkpointed trials; resume-vs-restart on
    # preemption hinges on whether a checkpoint exists at all).
    on_checkpoint: Optional[Callable[[int], None]] = None
    # Telemetry hooks (katib_tpu/telemetry.py), None when telemetry is off:
    # on_report is the watchdog heartbeat fired on every ctx.report (and on
    # subprocess output/scrape activity — the executor calls it directly);
    # on_subprocess re-points /proc sampling at the spawned child pids.
    on_report: Optional[Callable[[], None]] = None
    on_subprocess: Optional[Callable[[List[int]], None]] = None
    # Tracing (katib_tpu.tracing): bound by the scheduler when tracing is
    # on. The runtime marks the compile boundary (first report ends the
    # `compile` span and opens `steps`) and spans checkpoint saves/restores
    # and obslog flush barriers. All None when tracing is disabled — the
    # hot path then pays one attribute check per report.
    tracer: Optional[Any] = None
    trace_id: Optional[str] = None
    trace_parent: Optional[str] = None
    # AOT compile service handoff (katib_tpu/compilesvc): the WarmProgram
    # for this trial's dispatch group when the service compiled it ahead of
    # dispatch — fingerprint + the jax.stages.Compiled executable, callable
    # with concrete arrays matching the probe's avals. None when the
    # service is off, the program is cold/evicted, or the template has no
    # probe; trial code must treat it as an optional fast path and fall
    # back to its own jit (which the shared persistent XLA cache still
    # amortizes).
    compiled_program: Optional[Any] = None
    # Step clock (runtime/stepstats.py) — bound by the scheduler when
    # runtime.step_stats is on. Every report marks one step and freshly
    # completed perf windows are written through the observation store
    # under the reserved katib-tpu/perf/ namespace. None when the plane is
    # off: the hot path then pays one attribute check per report.
    step_clock: Optional[Any] = None

    def bind_trace(self, tracer, experiment: str, trace_id: str, parent_id: str) -> None:
        """Attach the trial's trace context (scheduler-side hook)."""
        self.tracer = tracer
        self.trace_id = trace_id
        self.trace_parent = parent_id
        self._trace_experiment = experiment
        self._compile_span = None
        self._steps_span = None
        self._report_count = 0
        self._ledger = tracing.StepLedger()

    def _stage(self, name: str) -> "tracing.StageSpan":
        return tracing.StageSpan(
            self.tracer, name, getattr(self, "_trace_experiment", self.experiment_name),
            self.trace_id, self.trace_parent,
        )

    def span(self, name: str, parent: Optional[str] = None, **attrs):
        """``with ctx.span("load_data"):`` — a stage of the trial function as
        a child of the lifecycle span that is open (``compile`` up to the
        first report, ``steps`` after it), and as ``katib:<name>`` in any
        profiler trace. A shared no-op where tracing is off."""
        if self.tracer is None:
            return tracing._NOOP_CM
        if parent is None:
            stage = getattr(self, "_compile_span", None) or getattr(self, "_steps_span", None)
            open_span = stage.span if stage is not None else None
            parent = open_span.span_id if open_span is not None else self.trace_parent
        return self.tracer.span(
            name, getattr(self, "_trace_experiment", self.experiment_name),
            self.trace_id, parent, **attrs,
        )

    def watch_step(self, step_fn: Callable) -> Callable:
        """Wrap the trial's step function so that the ``steps`` span's ledger
        (tracing.StepLedger) sees each call: the seconds inside it (dispatch)
        and when it returned; each call is a ``katib:step`` annotation too.
        Arguments, outputs and donation are the wrapped function's. With no
        tracer bound this is the identity."""
        ledger = getattr(self, "_ledger", None)
        if ledger is None:
            return step_fn
        clock, stepped = ledger.clock, ledger.stepped
        enter, leave = tracing.enter_annotation, tracing.leave_annotation

        def step(*args, **kwargs):
            note = enter("step")
            t_call = clock()
            try:
                out = step_fn(*args, **kwargs)
            finally:
                t_return = clock()
                leave(note)
            stepped(t_call, t_return)
            return out

        return step

    def count(self, **counters: float) -> None:
        """Counters of the program's own (a routed layer's loads, say) for the
        report interval that is open: the step ledger sums them into the
        interval's row under their names (tracing.StepLedger). The values are
        the caller's, fetched however it fetched its metrics; this call waits
        for nothing. With no tracer bound it does nothing."""
        ledger = getattr(self, "_ledger", None)
        if ledger is not None:
            ledger.count(counters)

    def _trace_fn_start(self) -> None:
        """Executor hook: the trial function is about to run. Everything up
        to the first report is attributed to `compile` (trace-and-compile of
        the train step dominates it on JAX workloads); what JAX times of it
        on this thread becomes its children."""
        if self.tracer is not None:
            self._compile_span = self._stage("compile")
            if self._compile_span.span is not None:
                tracing.route_compile_events()
        if self.step_clock is not None:
            from . import stepstats

            self._step_clock_token = stepstats.activate([self.step_clock])

    def _trace_mark_report(self, t_entry: Optional[float] = None) -> None:
        """First report = compile boundary: end `compile`, open `steps`."""
        self._report_count = getattr(self, "_report_count", 0) + 1
        cs = getattr(self, "_compile_span", None)
        if cs is not None:
            ledger = getattr(self, "_ledger", None)
            first = ledger.first_report(t_entry) if ledger is not None and t_entry is not None else {}
            self._end_compile(cs, first_report=True, **first)
            self._compile_span = None
            self._steps_span = self._stage("steps")

    def _end_compile(self, cs: "tracing.StageSpan", **attrs) -> None:
        if cs.span is not None:
            short = tracing.unroute_compile_events(
                self.tracer, getattr(self, "_trace_experiment", self.experiment_name),
                self.trace_id, cs.span.span_id,
            )
            if short:
                attrs["short_events"] = short
        cs.end(**attrs)

    def _trace_fn_end(self) -> None:
        """Executor hook: the trial function returned/unwound."""
        token = getattr(self, "_step_clock_token", None)
        if token is not None:
            from . import stepstats

            stepstats.deactivate(token)
            self._step_clock_token = None
        if self.tracer is None:
            return
        cs = getattr(self, "_compile_span", None)
        if cs is not None:
            # the function never reported: the whole run was one opaque
            # stretch — keep it labeled compile with the zero-report marker
            self._end_compile(cs, reports=0)
            self._compile_span = None
        ss = getattr(self, "_steps_span", None)
        if ss is not None:
            ledger = getattr(self, "_ledger", None)
            ss.end(
                **(ledger.attrs() if ledger is not None else {}),
                reports=getattr(self, "_report_count", 0),
            )
            self._steps_span = None

    def report(self, **metrics: float) -> None:
        """Push metrics; raises katib_tpu.runtime.metrics.EarlyStopped when all
        early-stopping rules have tripped, TrialPreempted when the fair-share
        policy needs this trial's chips (metrics are persisted first — save
        your checkpoint BEFORE reporting and preemption loses nothing)."""
        ledger = getattr(self, "_ledger", None)
        if ledger is None:
            if self.tracer is not None:
                self._trace_mark_report()
            self._push(metrics, None)
            return
        t_entry = ledger.clock()
        self._trace_mark_report(t_entry)
        note = tracing.enter_annotation("report")
        try:
            self._push(metrics, ledger)
        finally:
            # an unwind (early stop, kill, preemption) leaves through here too
            tracing.leave_annotation(note)
            ledger.reported(t_entry)

    def _push(self, metrics: Dict[str, float], ledger) -> None:
        if self.on_report is not None:
            self.on_report()  # watchdog heartbeat BEFORE a possible unwind
        sc = self.step_clock
        if sc is not None:
            from . import stepstats

            sc.mark(metrics)
            rows = sc.drain()
            if rows:
                # perf rows land BEFORE the report so the kill/preempt
                # flush barrier in MetricsReporter.report makes them
                # durable ahead of any unwind
                self.reporter.store.report_observation_log(
                    self.trial_name, stepstats.perf_logs(rows)
                )
        if ledger is None:
            self.reporter.report(**metrics)
            return
        note = tracing.enter_annotation("store_write")
        t0 = ledger.clock()
        try:
            self.reporter.report(**metrics)
        finally:
            ledger.stored(ledger.clock() - t0)
            tracing.leave_annotation(note)

    def flush_metrics(self) -> None:
        """Durability barrier for write-behind observation stores
        (db/store.py BufferedObservationStore): returns once every metric
        reported so far is persisted. The runtime calls it on checkpoint
        save and before TrialPreempted/TrialKilled unwind; trial code only
        needs it around its own external side effects."""
        with self.span("obslog_flush", parent=self.trace_parent):
            self.reporter.store.flush()

    @property
    def preempt_requested(self) -> bool:
        """True once the fair-share policy selected this trial as a
        preemption victim. Long in-step loops that rarely report can poll
        this, save a checkpoint, and call report() (which raises
        TrialPreempted) to yield their devices promptly."""
        ev = getattr(self.reporter, "preempt_event", None)
        return ev is not None and ev.is_set()

    def profile(self, enabled: Optional[bool] = None):
        """Context manager: capture a JAX profiler (xplane) trace of the
        enclosed steps into ``<workdir>/profile`` — surfaced by the UI at
        ``/api/experiments/<e>/trials/<t>/profile``. No-op without a workdir
        so trial code can call it unconditionally (SURVEY.md §5). ``enabled``
        defaults from ``$KATIB_TPU_PROFILE`` (the executor stamps it on trial
        subprocesses), so an operator can switch profiling fleet-wide without
        touching trial code; an explicit True/False wins."""
        from .profiling import profile_trace

        return profile_trace(self.workdir, enabled=enabled)

    def jax_devices(self):
        """The trial's allocated devices that are real jax.Device objects.

        The scheduler hands out abstract int slots when no accelerator is
        attached to allocation (subprocess-only experiments); trials building
        meshes must use this filtered view — empty means "use jax.devices()".
        """
        import jax

        return [d for d in (self.devices or []) if isinstance(d, jax.Device)]

    def mesh(self, axis_names=("data",), shape=None):
        """Build a jax.sharding.Mesh over this trial's allocated devices.

        Default: 1-D data mesh. Pass shape for multi-axis (e.g. shape=(2, 4),
        axis_names=("data", "model")), or set ``resources.topology``
        ("2x4") in the trial template — it becomes the default shape when
        the axis count matches.
        """
        import numpy as np
        from jax.sharding import Mesh

        devices = self.jax_devices()
        if not devices:
            from ..utils.backend import require_devices

            # bounded probe, not a raw jax.devices(): a trial building a
            # mesh on a wedged backend must fail fast, not hang (KTI304)
            devices = require_devices()
        arr = np.array(devices)
        if shape is None and self.topology and len(axis_names) > 1:
            from ..api.spec import parse_topology

            dims = parse_topology(self.topology)
            if dims is not None and len(dims) == len(axis_names):
                shape = tuple(dims)
        if shape is not None:
            arr = arr.reshape(shape)
        else:
            arr = arr.reshape((-1,) * 1)
            if len(axis_names) > 1:
                raise ValueError(
                    "pass shape= for multi-axis meshes (or set "
                    "resources.topology with one dim per axis)"
                )
        return Mesh(arr, axis_names)

    def checkpoint_store(self, subdir: Optional[str] = None):
        """Typed orbax-backed save/restore (runtime/checkpoints.py) rooted at
        this trial's checkpoint dir (the PBT lineage dir when the suggester
        provides one) or its workdir — the elastic-resume idiom: restore the
        latest step at start, save per epoch; a restarted trial
        (max_trial_restarts, PBT exploit child, controller resume) continues
        instead of starting over."""
        from .checkpoints import store_for

        store = store_for(self.checkpoint_dir, self.workdir, subdir)
        notify, orig_save, orig_restore = self.on_checkpoint, store.save, store.restore

        def _save(step, state, _notify=notify, _orig=orig_save):
            with self.span("checkpoint_save", parent=self.trace_parent, step=int(step)):
                _orig(step, state)
            if _notify is not None:
                _notify(step)
            # every save is a durability point: a preemption decided against
            # this freshly-checkpointed trial must find its metrics on disk
            self.flush_metrics()

        def _restore(step=None, template=None, _orig=orig_restore):
            with self.span("checkpoint_restore", parent=self.trace_parent) as span:
                span.set(found=False)
                restored = _orig(step=step, template=template)
                span.set(found=restored is not None)
                return restored

        store.save = _save  # instance-level shadow; CheckpointStore API unchanged
        store.restore = _restore
        return store

    def param(self, name: str, default: Optional[str] = None) -> Optional[str]:
        return self.assignments.get(name, default)

    def param_float(self, name: str, default: Optional[float] = None) -> Optional[float]:
        v = self.assignments.get(name)
        return float(v) if v is not None else default

    def param_int(self, name: str, default: Optional[int] = None) -> Optional[int]:
        v = self.assignments.get(name)
        return int(float(v)) if v is not None else default
