"""Trial scheduler — gang device allocation + trial lifecycle supervision.

TPU-native replacement for the reference's trial controller + kube-scheduler
pair (pkg/controller.v1beta1/trial/trial_controller.go): instead of creating
K8s jobs and mapping their conditions back via GJSON, the scheduler

- gang-allocates devices: a trial asks for ``resources.num_devices`` TPU
  chips and is dispatched only when that many are free (all-or-nothing, like
  a gang-scheduled JAXJob; SURVEY.md §7 layer 4);
- runs the trial via an executor on a worker thread;
- on completion folds the observation log into the trial record
  (UpdateTrialStatusObservation, trial_controller_util.go:124-217) and applies
  the success/failure/metrics-unavailable classification
  (trial_controller_util.go:42-122);
- pushes a completion event that wakes the experiment controller — replacing
  K8s watch events and the 1-second metrics requeue
  (trial_controller.go:182-185) with direct event delivery.

Dispatch order is governed by the fair-share policy (controller/fairshare.py):
priority classes, per-experiment device quotas, deficit-weighted fair-share
ordering with aging, backfill around a blocked gang's reservation, and
checkpoint-based preemption of lower-priority running trials. When no
experiment sets any fair-share knob, the legacy arrival-order path runs
unchanged.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
import traceback
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

from ..api.spec import CollectorKind, ObjectiveType, UNAVAILABLE_METRIC_VALUE
from ..api.status import Experiment, Trial, TrialCondition
from ..db.state import ExperimentStateStore
from ..db.store import ObservationStore
from ..runtime.context import TrialContext
from ..runtime.metrics import EarlyStoppingMonitor, MetricsReporter
from .executor import (
    ExecutionResult,
    InProcessExecutor,
    MultiHostExecutor,
    SubprocessExecutor,
    TrialExecution,
    TrialOutcome,
)

log = logging.getLogger("katib_tpu.scheduler")


@dataclass
class TrialEvent:
    experiment_name: str
    trial_name: str
    condition: TrialCondition


class DeviceAllocator:
    """All-or-nothing chip allocator.

    Legacy shape (``plane=None``): a fixed free list — acquisition and
    release shuffle devices between the list and the holders, and the pool
    can never change size. With a supervised device plane attached
    (controller/deviceplane.py), every gang allocation is a revocable
    LEASE: the plane tracks holders and heartbeats, reclaims zombie leases
    on expiry, removes lost devices from custody, and swaps in a failover
    pool when the backend dies — so ``total``/``free_count`` are live
    views, not constants. The legacy path is byte-identical when no plane
    is attached (KATIB_TPU_DEVICE_PLANE=0)."""

    def __init__(self, devices: Sequence[Any], plane=None):
        self._lock = threading.Lock()
        self._plane = plane
        if plane is not None:
            plane.adopt_pool(devices)
            self._free = []
            self._total = len(list(devices))
        else:
            self._free: List[Any] = list(devices)
            self._total = len(self._free)

    def acquire(
        self, n: int, holder: str = "", experiment: str = ""
    ) -> Optional[List[Any]]:
        if self._plane is not None:
            return self._plane.acquire(n, holder=holder, experiment=experiment)
        with self._lock:
            if n > len(self._free):
                return None
            taken, self._free = self._free[:n], self._free[n:]
            return taken

    def release(self, devices: Sequence[Any]) -> None:
        if self._plane is not None:
            self._plane.release(devices)
            return
        with self._lock:
            self._free.extend(devices)

    @property
    def free_count(self) -> int:
        if self._plane is not None:
            return self._plane.free_count
        with self._lock:
            return len(self._free)

    @property
    def total(self) -> int:
        if self._plane is not None:
            return self._plane.total
        return self._total


class TrialScheduler:
    def __init__(
        self,
        state: ExperimentStateStore,
        obs_store: ObservationStore,
        devices: Optional[Sequence[Any]] = None,
        db_path: Optional[str] = None,
        workdir_root: Optional[str] = None,
        events=None,
        metrics=None,
        trial_timeout: Optional[float] = None,
        max_trial_restarts: int = 0,
        poll_interval: Optional[float] = None,
        devices_per_host: Optional[int] = None,
        queue_stall_seconds: float = 120.0,
        aging_seconds: float = 60.0,
        preemption_grace_seconds: float = 30.0,
        tracer=None,
        telemetry=None,
        compile_service=None,
        compile_gate_seconds: float = 0.0,
        fused_population: bool = True,
        population_chunk_generations: int = 16,
        population_stream: bool = False,
        suggestion_prefetch: Optional[Callable[[str], None]] = None,
        multifidelity=None,
        device_plane=None,
        journal=None,
        step_stats=None,
    ):
        from .fairshare import FairSharePolicy
        from ..tracing import install_log_context

        install_log_context()  # experiment=/trial=/trace_id= log stamping
        self.recorder = events
        self.metrics_registry = metrics
        self.tracer = tracer  # katib_tpu.tracing.Tracer (None = no tracing)
        self.telemetry = telemetry  # telemetry.ResourceSampler (None = off)
        # async suggestion pipeline hook (ISSUE 10): called with the
        # experiment name whenever a trial reaches a terminal condition, so
        # the SuggestionService can precompute the next batch before the
        # reconcile loop consults it
        self.suggestion_prefetch = suggestion_prefetch
        self._queue_spans: Dict[str, Any] = {}  # trial -> open queue_wait span
        if devices is None:
            devices = list(range(8))  # abstract slots when JAX not involved
        devices = list(devices)
        if devices_per_host and devices_per_host != len(devices):
            from .deviceplane import is_abstract_pool

            if not is_abstract_pool(devices):
                # real devices are what the host has: a cap would drop chips
                # from the pool without a word, a larger number adds none
                raise ValueError(
                    f"devices_per_host={devices_per_host} does not match the "
                    f"{len(devices)} real device(s) given; pass the devices "
                    "to pool instead"
                )
            devices = devices[:devices_per_host]  # sizes an abstract pool
        # -- supervised device plane (controller/deviceplane.py) -------------
        # None = disabled: the allocator below runs the legacy free-list
        # path byte-identically and every consult is one `is None` check
        self.device_plane = device_plane
        self.allocator = DeviceAllocator(devices, plane=device_plane)
        if device_plane is not None:
            # device loss (probe failure, heartbeat miss, chaos revocation)
            # converts the holding gang into a checkpoint-preemption; pool
            # changes (zombie reclaim, failover) re-run the dispatch pass
            device_plane.set_loss_handler(self._on_devices_lost)
            device_plane.set_kill_handler(self._chaos_kill_holder)
            device_plane.set_pool_changed_handler(self._on_pool_changed)
        self._unit_devices: Dict[str, List[Any]] = {}  # unit key -> gang devices
        self.state = state
        self.obs_store = obs_store
        self.events: "queue.Queue[TrialEvent]" = queue.Queue()
        self.workdir_root = workdir_root
        self.trial_timeout = trial_timeout
        self.max_trial_restarts = max_trial_restarts
        self._restarts: Dict[str, int] = {}
        self._in_process = InProcessExecutor(obs_store)
        self._subprocess = SubprocessExecutor(obs_store, db_path=db_path)
        self._multihost = MultiHostExecutor(obs_store, db_path=db_path)
        if poll_interval:
            self._subprocess.POLL_INTERVAL = poll_interval
            self._multihost.POLL_INTERVAL = poll_interval
        self._handles: Dict[str, TrialExecution] = {}
        self._pending: "queue.Queue" = queue.Queue()
        self._lock = threading.Lock()
        self._waiting: List = []  # trials waiting for devices
        self._threads: List[threading.Thread] = []
        self._checkpoint_dirs: Dict[str, str] = {}
        self._quarantined = 0  # devices held by abandoned zombie trials
        self._shutdown = threading.Event()
        self._intentional_kills: set = set()  # kill() targets, vs shutdown kills
        self._dispatch_paused = 0  # dispatch_barrier depth (batch submits)
        # -- fair-share scheduling state (controller/fairshare.py) -----------
        self.queue_stall_seconds = queue_stall_seconds
        self.preemption_grace_seconds = preemption_grace_seconds
        self._policy = FairSharePolicy(aging_seconds=aging_seconds)
        self._seq_counter = 0                      # arrival order for the queue
        self._enqueue_seq: Dict[str, int] = {}     # trial -> arrival seq
        self._enqueued_at: Dict[str, float] = {}   # trial -> pending since
        self._stall_emitted: set = set()           # TrialQueueStalled once/stint
        self._usage: Dict[str, int] = {}           # experiment -> devices held
        self._running: Dict[str, Any] = {}         # unit key -> RunningUnit
        self._preempting: set = set()              # trials signalled to preempt
        self._last_checkpoint: Dict[str, float] = {}  # trial -> last ckpt save
        self._gauged_experiments: set = set()      # queue gauges to zero out
        # backfill reservation: the first blocked unit in policy order
        # earmarks every chip released while it stays blocked (its credits);
        # backfill may only use free chips beyond the credits
        self._head_key: Optional[str] = None
        self._head_credits = 0
        # -- AOT compile service (compilesvc/service.py) ---------------------
        # None = disabled: every consult below is one `is None` check and
        # dispatch is byte-identical to the legacy path
        self.compile_service = compile_service
        self.compile_gate_seconds = compile_gate_seconds
        # -- fused population loops (runtime/population.py) ------------------
        # off, or for any pack that is not an opted-in fused sweep, the
        # PackedTrialExecutor path below is byte-identical to before
        self.fused_population = fused_population
        self.population_chunk_generations = population_chunk_generations
        self.population_stream = population_stream
        # -- multi-fidelity engine (controller/multifidelity.py) -------------
        # None = disabled: every consult below is one `is None` check and
        # trial finalization is byte-identical to the legacy path; with an
        # engine attached only `algorithm: asha` experiments use it
        self.multifidelity = multifidelity
        # -- recovery journal (controller/recovery.py, ISSUE 14) -------------
        # None = disabled: dispatch and terminal transitions leave no intent
        # records and every consult below is one `is None` check
        self.journal = journal
        # -- step-statistics plane (controller/stepstats.py, ISSUE 20) -------
        # None = disabled: no clocks are bound to contexts, no perf rows are
        # written, and every consult below is one `is None` check
        self.step_stats = step_stats
        self._gate_since: Dict[Any, float] = {}  # group key -> hold start
        self._gate_held: Dict[str, float] = {}   # trial -> hold start (spans)
        self._gate_timer_live = False            # one wake timer per hold
        if compile_service is not None:
            # a program turning warm (or failing) re-runs the dispatch pass;
            # the service notifies with NO service lock held, so the only
            # lock edge is scheduler->service (from the dispatch walk)
            compile_service.add_listener(self._on_compile_transition)

    # -- submission ----------------------------------------------------------

    LINEAGE_LABEL = "checkpoint-lineage"

    def _tr(self):
        """The active tracer, or None when tracing is off — every
        instrumentation site guards on this one cheap check."""
        t = self.tracer
        return t if (t is not None and t.enabled) else None

    def _tm(self):
        """The active resource sampler, or None when telemetry is off —
        same one-boolean-check contract as _tr()."""
        t = self.telemetry
        return t if (t is not None and t.enabled) else None

    def _cs(self):
        """The active compile service, or None when disabled — same
        one-check contract as _tr()/_tm()."""
        s = self.compile_service
        return s if (s is not None and s.active) else None

    def _mf(self):
        """The multi-fidelity engine, or None when runtime.multifidelity is
        off — same one-check contract as _tr()/_tm()/_cs()."""
        return self.multifidelity

    def _dp(self):
        """The supervised device plane, or None when runtime.device_plane
        is off — same one-check contract as _tr()/_tm()/_cs()/_mf()."""
        return self.device_plane

    def _on_devices_lost(self, devices: Sequence[Any], reason: str) -> None:
        """Device-plane loss handler (no plane lock held): every running
        unit holding a lost device converts into a checkpoint-preemption —
        the cooperative signal first (victims checkpoint-and-yield at their
        next report through the PR 2/9 freeze machinery), the grace-window
        kill as escalation. Requeued members resume from their last
        checkpoint on the surviving devices bit-identically, or re-run
        clean without one — exactly the fair-share preemption contract."""
        lost = set(devices)
        victims = []
        with self._lock:
            for key, unit in self._running.items():
                held = self._unit_devices.get(key, ())
                if any(d in lost for d in held):
                    unit.preempt_signaled = True
                    self._preempting.update(unit.trial_names)
                    victims.append(unit)
        for unit in victims:
            log.warning(
                "device loss (%s): preempting %s to requeue on surviving "
                "devices", reason, ",".join(unit.trial_names),
            )
            for h in unit.handles:
                h.preempt()
            if self.preemption_grace_seconds:
                timer = threading.Timer(
                    self.preemption_grace_seconds,
                    lambda hs=list(unit.handles): [h.kill() for h in hs],
                )
                timer.daemon = True
                timer.start()
        if not self._shutdown.is_set():
            self._dispatch()

    def _chaos_kill_holder(self, holder: str) -> None:
        """Chaos process-kill injection (utils/chaos.py): hard-kill the
        holding unit, but through the preemption bookkeeping — a chaos
        kill models an external death, and the trial must requeue and
        recover exactly like a device-loss victim, not count as a
        deliberate kill()."""
        with self._lock:
            unit = self._running.get(holder)
            if unit is None:
                return
            unit.preempt_signaled = True
            self._preempting.update(unit.trial_names)
            handles = list(unit.handles)
        log.warning("chaos kill injected on %s", holder)
        for h in handles:
            h.kill()

    def _on_pool_changed(self) -> None:
        """Plane hook: devices re-entered the pool outside the normal
        release path (zombie-lease reclaim, revocation, failover) — run a
        dispatch pass so waiting gangs pick them up."""
        if not self._shutdown.is_set():
            self._dispatch()

    def _on_compile_transition(self, key) -> None:
        """CompileService listener (worker thread, no service lock held): a
        group turned warm or was quarantined — re-run the dispatch pass so
        gate-held units start (or fall back to inline compilation)."""
        if not self._shutdown.is_set():
            self._dispatch()

    def _trace_end_trial(self, exp_name: str, trial: Trial) -> None:
        """End the trial's root span once it is terminal (idempotent).
        Called AFTER all child spans closed so parents outlive children."""
        tr = self._tr()
        if tr is not None and trial.is_terminal:
            attrs = {}
            if self.workdir_root:
                import os
                # deep-profile linkage (runtime/profiling.py): when the trial
                # captured xplane dumps, stamp their location on the root
                # span so `katib-tpu trace <exp>` shows which trials have a
                # profiler trace behind their spans. _record_terminal's
                # retainRun cleanup ran already, so the stamp only lands
                # when the dumps actually survive on disk (retained,
                # failed/killed, or rung-paused workdirs).
                from ..runtime.profiling import list_profile_artifacts

                workdir = os.path.join(self.workdir_root, exp_name, trial.name)
                if list_profile_artifacts(workdir):
                    from ..runtime.profiling import PROFILE_DIRNAME

                    attrs["profileDir"] = os.path.join(workdir, PROFILE_DIRNAME)
            tr.end_trial(
                exp_name, trial.name,
                outcome=trial.condition.value, reason=trial.current_reason,
                **attrs,
            )

    def submit(
        self,
        exp: Experiment,
        trial: Trial,
        checkpoint_dir: Optional[str] = None,
        dispatch: bool = True,
    ) -> None:
        """Queue a trial. ``dispatch=False`` defers the dispatch pass so a
        caller submitting a batch (one reconcile's worth of suggestions) can
        queue them all first and call :meth:`dispatch` once — without this,
        the first packable trial of a batch would start solo before its
        pack-mates arrive (controller/packing.py)."""
        if checkpoint_dir:
            # Persisted marker (the _checkpoint_dirs entry is transient —
            # popped on start): this trial trains FROM a parent checkpoint,
            # so its metrics reflect inherited training, and duplicate-reuse
            # must never treat it as a from-scratch result for the same
            # assignments — in either direction (advisor round-4 finding:
            # the old guard only blocked lineage trials as reuse TARGETS).
            trial.labels[self.LINEAGE_LABEL] = "1"
        tr = self._tr()
        admission = None
        if tr is not None:
            # one trace per trial: the controller may already have begun it
            # at suggestion time; direct submits (resume, tests) begin here
            root = tr.begin_trial(exp.name, trial.name)
            admission = tr.start_span(
                "admission", exp.name, root.trace_id, root.span_id,
                attrs={"lineage": bool(checkpoint_dir)},
            )
        trial.set_condition(TrialCondition.PENDING, "TrialPending", "waiting for devices")
        self.state.update_trial(trial)
        if self.metrics_registry is not None:
            self.metrics_registry.inc("katib_trial_created_total", experiment=exp.name)
        if self.recorder is not None:
            self.recorder.event(exp.name, "Trial", trial.name, "TrialCreated", "Trial is created")
        if checkpoint_dir:
            with self._lock:
                self._checkpoint_dirs[trial.name] = checkpoint_dir
        elif (
            # the persisted label, not the transient checkpoint_dir arg: a
            # resumed lineage trial can be resubmitted with
            # checkpoint_dir=None (experiment.py resume path swallows
            # _checkpoint_dir_for failures) and must still never consume a
            # from-scratch result
            not trial.labels.get(self.LINEAGE_LABEL)
            and exp.spec.reuse_duplicate_results
            and self._reuse_duplicate(exp, trial)
        ):
            # finalized from a prior identical-assignment success; never
            # reused for checkpoint-lineage trials (PBT exploit/explore
            # trains FROM a parent checkpoint — same params, different run)
            if tr is not None:
                tr.end_span(admission, reused=True)
                self._trace_end_trial(exp.name, trial)
            return
        if tr is not None:
            tr.end_span(admission)
        cs = self._cs()
        if cs is not None:
            # AOT compile request for this trial's dispatch group — dict hit
            # after the first trial of a group; the compile itself runs on
            # the service's worker pool, never on this thread
            trace_ctx = None
            if tr is not None:
                root = tr.trial_root(exp.name, trial.name)
                if root is not None:
                    trace_ctx = (root.trace_id, root.span_id)
            try:
                cs.request(exp, trial, trace=trace_ctx)
            except Exception:
                log.debug("compile service request failed", exc_info=True)
        with self._lock:
            self._stamp_enqueue(exp, trial)
            self._waiting.append((exp, trial))
        if dispatch:
            self._dispatch()

    def _stamp_enqueue(self, exp: Experiment, trial: Trial) -> None:
        """Record arrival order + pending-since for the fair-share queue;
        caller holds the scheduler lock."""
        self._seq_counter += 1
        self._enqueue_seq[trial.name] = self._seq_counter
        self._enqueued_at[trial.name] = time.time()
        tr = self._tr()
        if tr is not None:
            root = tr.trial_root(exp.name, trial.name)
            if root is not None:
                self._queue_spans[trial.name] = tr.start_span(
                    "queue_wait", exp.name, root.trace_id, root.span_id
                )

    def _clear_enqueue(self, trial_name: str, experiment: str = "") -> None:
        """Drop a trial's queue bookkeeping (dispatched or killed while
        pending); caller holds the scheduler lock."""
        self._enqueue_seq.pop(trial_name, None)
        self._enqueued_at.pop(trial_name, None)
        span = self._queue_spans.pop(trial_name, None)
        gated_since = self._gate_held.pop(trial_name, None)
        if span is not None:
            tr = self._tr()
            if tr is not None:
                # stall flag from PR 2's queue bookkeeping: was this wait
                # long enough that TrialQueueStalled fired for it?
                attrs: Dict[str, Any] = {
                    "stalled": trial_name in self._stall_emitted
                }
                now = time.time()
                if gated_since is not None:
                    # Perfetto distinction: "waiting for chips" vs "waiting
                    # for XLA" — this wait was (partly) the compile gate
                    attrs["compileGated"] = True
                    attrs["compileGateSeconds"] = round(now - gated_since, 3)
                    if experiment:
                        tr.record_span(
                            "compile_gate", experiment, span.trace_id,
                            span.parent_id, start=gated_since, end=now,
                        )
                tr.end_span(span, **attrs)
        self._stall_emitted.discard(trial_name)

    def dispatch(self) -> None:
        """Start every waiting trial/pack whose gang allocation fits (the
        public form of the internal dispatch pass, for deferred submits)."""
        self._dispatch()

    def dispatch_barrier(self):
        """Context manager making a batch submission atomic with respect to
        dispatch: passes triggered while the barrier is held (a compile
        finishing in the service, a concurrent trial releasing its gang)
        return immediately, and one pass runs at exit. Without this, a
        dispatch landing between a batch's submit() calls sees a PARTIAL
        batch — which split a fused population sweep into two smaller
        packs, each running a full independent sweep (doubled population
        rows, wrong population semantics), and starts packable trials solo
        before their pack-mates arrive."""
        import contextlib

        @contextlib.contextmanager
        def barrier():
            with self._lock:
                self._dispatch_paused += 1
            try:
                yield
            finally:
                with self._lock:
                    self._dispatch_paused -= 1
                self._dispatch()

        return barrier()

    def _reuse_duplicate(self, exp: Experiment, trial: Trial) -> bool:
        """Opt-in duplicate-result reuse (spec.reuse_duplicate_results): if a
        Succeeded trial of this experiment has exactly the same parameter
        assignments, copy its observation log to this trial and finalize it
        Succeeded without running the workload. No reference counterpart —
        on TPU, a duplicate suggestion (small discrete spaces, categorical
        resampling) would otherwise re-burn a full training run.

        Scope, by design: only PREVIOUSLY COMPLETED trials match. Identical
        suggestions dispatched in the same reconcile batch (parallel > 1)
        all execute in full — deduping against in-flight twins would need a
        subscription on their completion and buys little, since duplicate
        suggestions mostly arrive across reconciles as a search converges.
        Checkpoint-lineage trials (persisted ``checkpoint-lineage`` label)
        are excluded as sources: their metrics reflect training inherited
        from a parent checkpoint, not a from-scratch run with these
        assignments."""
        key = tuple(sorted((a.name, a.value) for a in trial.parameter_assignments))
        if not key:
            return False  # nothing to match on; run the trial
        source = None
        for t in self.state.list_trials(exp.name):
            if (
                t.name != trial.name
                and t.condition == TrialCondition.SUCCEEDED
                and t.labels.get(self.LINEAGE_LABEL) != "1"
                and tuple(sorted((a.name, a.value) for a in t.parameter_assignments)) == key
            ):
                source = t
                break
        if source is None:
            return False
        logs = self.obs_store.get_observation_log(source.name)
        if logs:
            self.obs_store.report_observation_log(trial.name, logs)
        trial.observation = self.obs_store.folded(
            trial.name, exp.spec.objective.all_metric_names()
        )
        # pass through RUNNING so start_time is stamped — rung-cohort
        # algorithms (hyperband) sort trials by start_time, and a None
        # there would silently misplace the reused trial in its bracket
        trial.set_condition(
            TrialCondition.RUNNING, "TrialRunning",
            f"reusing result of trial {source.name}",
        )
        trial.set_condition(
            TrialCondition.SUCCEEDED,
            "DuplicateResultReused",
            f"reused result of trial {source.name} (identical assignments)",
        )
        self._record_terminal(exp, trial)
        self.events.put(TrialEvent(exp.name, trial.name, trial.condition))
        return True

    def kill(self, trial_name: str) -> None:
        """Early-stop / parallel-shrink kill (reference deleteTrials) — a
        deliberate decision, recorded so a later shutdown can't relabel the
        trial SchedulerShutdown and get it wrongly requeued on resume."""
        with self._lock:
            self._intentional_kills.add(trial_name)
            for i, (exp, t) in enumerate(self._waiting):
                if t.name == trial_name:
                    self._waiting.pop(i)
                    self._checkpoint_dirs.pop(trial_name, None)
                    self._clear_enqueue(trial_name, exp.name)
                    t.set_condition(TrialCondition.KILLED, "TrialKilled", "killed while pending")
                    self.state.update_trial(t)
                    self._trace_end_trial(exp.name, t)
                    self.events.put(TrialEvent(exp.name, t.name, t.condition))
                    return
        h = self._handles.get(trial_name)
        if h is not None:
            h.kill()
            return
        mf = self._mf()
        if mf is not None:
            # neither queued nor running: a rung-paused multi-fidelity trial
            # is killed in place and removed from its rung's candidates
            mf.kill_paused(trial_name, self)

    def kill_all(self) -> None:
        """Controller shutdown: kill everything, marking trials with the
        SchedulerShutdown reason so a cross-process resume
        (ExperimentController.load_experiment) can requeue them — shutdown is
        an artifact of the controller's lifetime, not a search decision."""
        self._shutdown.set()
        tr = self._tr()
        with self._lock:
            waiting = list(self._waiting)
            self._waiting.clear()
            self._enqueue_seq.clear()
            self._enqueued_at.clear()
            self._stall_emitted.clear()
            self._head_key, self._head_credits = None, 0
            self._gate_since.clear()
            self._gate_held.clear()
            queue_spans = dict(self._queue_spans)
            self._queue_spans.clear()
        for exp, t in waiting:
            t.set_condition(TrialCondition.KILLED, "SchedulerShutdown", "scheduler shutdown")
            self.state.update_trial(t)
            if tr is not None:
                tr.end_span(queue_spans.get(t.name), aborted="shutdown")
                self._trace_end_trial(exp.name, t)
        for h in list(self._handles.values()):
            h.kill()

    def active_count(self) -> int:
        with self._lock:
            return len(self._waiting) + len(self._handles)

    def is_active(self, trial_name: str) -> bool:
        with self._lock:
            return trial_name in self._handles or any(
                t.name == trial_name for _, t in self._waiting
            )

    def join(self, timeout: Optional[float] = None) -> None:
        deadline = None if timeout is None else time.time() + timeout
        for t in list(self._threads):
            remaining = None if deadline is None else max(0.0, deadline - time.time())
            t.join(timeout=remaining)

    def quiesce(self, experiment_name: str, timeout: float = 10.0) -> bool:
        """Wait until no trial of this experiment is queued or holds a worker
        slot (and hence a gang allocation). A trial's terminal condition is
        persisted BEFORE its worker's finally-block releases the devices, so
        an observer that saw the experiment complete can be a few hundred
        microseconds ahead of the allocator; callers that are about to hand
        the chips to something else wait here instead of racing. Returns
        False on timeout (e.g. an abandoned zombie trial being reaped)."""
        deadline = time.time() + timeout
        while time.time() < deadline:
            with self._lock:
                # snapshot: _run_trial's finally pops _handles under its own
                # lock stints, and get_trial yields the GIL mid-generator
                handle_names = list(self._handles)
                waiting = [t.experiment_name for _, t in self._waiting]
            busy = any(
                self.state.get_trial(experiment_name, n) is not None
                for n in handle_names
            ) or experiment_name in waiting
            if not busy:
                return True
            time.sleep(0.005)
        return False

    # -- dispatch loop -------------------------------------------------------

    def _dispatch(self) -> None:
        """Start every waiting trial/pack whose gang allocation fits.

        Waiting trials are first grouped into dispatch units by
        packing.plan_packs: packable same-template trials of one experiment
        merge into packs of up to K = pack_capacity(exp) members sharing ONE
        gang allocation and one compiled program; everything else dispatches
        solo through the unchanged per-trial path.

        Units are then walked in fair-share policy order (priority + aging,
        deficit-weighted fair share, arrival order — controller/fairshare.py)
        with quota enforcement, backfill-vs-reservation, and preemption
        planning. When no experiment in the system sets any fair-share knob,
        the walk degenerates to the legacy path: arrival order, every unit
        tries its allocation, misses requeue — FIFO preserved exactly."""
        from . import fairshare as fs
        from .packing import plan_packs

        now = time.time()
        with self._lock:
            if self._dispatch_paused:
                # a batch submission holds the dispatch barrier: this pass
                # would see a partial batch; the barrier exit re-runs it
                return
            self._threads = [t for t in self._threads if t.is_alive()]
            cs = self._cs()
            warm = None
            if cs is not None:
                # pack formation prefers units whose dispatch group already
                # has a warm executable (registry dict hit; advisory)
                def warm(exp, trial, _cs=cs):
                    try:
                        return _cs.is_warm(exp.spec, trial)
                    except Exception:
                        return False
            units = plan_packs(self._waiting, warm=warm)
            self._waiting = []
            entries: List[fs.QueueEntry] = []
            for exp, members in units:
                requested = max(exp.spec.trial_template.resources.num_devices, 1)
                entries.append(
                    fs.QueueEntry(
                        exp=exp,
                        trials=members,
                        needed=min(requested, self.allocator.total),
                        requested=requested,
                        seq=min(self._enqueue_seq.get(t.name, 0) for t in members),
                        enqueued_at=min(
                            self._enqueued_at.get(t.name, now) for t in members
                        ),
                        priority=fs.priority_of(exp),
                    )
                )
            fairshare_on = any(fs.uses_fairshare(e.exp) for e in entries) or any(
                u.fairshare for u in self._running.values()
            )
            ordered = (
                self._policy.order(entries, now)
                if fairshare_on
                else self._fingerprint_grouped(entries)
            )
            free = self.allocator.free_count
            leftover: List[fs.QueueEntry] = []
            head_seen = False
            if not fairshare_on:
                self._head_key, self._head_credits = None, 0
            for e in ordered:
                n = e.needed
                quota = fs.device_quota_of(e.exp)
                if quota is not None and self._usage.get(e.exp.name, 0) + n > quota:
                    # quota-blocked: holds no reservation — units behind it
                    # flow around freely
                    leftover.append(e)
                    continue
                if not fairshare_on and self._gate_hold(e, now):
                    # compile-gated: the unit's executable is still
                    # compiling in the service — hold it (units behind flow
                    # around, like a quota block) up to compile_gate_seconds,
                    # then fall back to inline compilation
                    leftover.append(e)
                    continue
                if fairshare_on:
                    if not head_seen and free < n:
                        # first blocked unit in policy order becomes the
                        # reserving head: chips released while it stays
                        # blocked accrue to its credits and cannot be
                        # backfilled, so its gang assembles monotonically
                        head_seen = True
                        if self._head_key != e.key:
                            self._head_key, self._head_credits = e.key, 0
                        self._head_credits = min(self._head_credits, n)
                        self._plan_preemption(e, free)
                        leftover.append(e)
                        continue
                    reserved = min(self._head_credits, free) if head_seen else 0
                    if free - reserved < n:
                        leftover.append(e)
                        continue
                devices = self.allocator.acquire(
                    n, holder=e.key, experiment=e.exp.name
                )
                if devices is None:
                    leftover.append(e)
                    continue
                free -= n
                if e.key == self._head_key:
                    self._head_key, self._head_credits = None, 0
                self._start_unit(e, devices)
            if fairshare_on and not head_seen:
                # the previous head dispatched or left the queue
                self._head_key, self._head_credits = None, 0
            self._waiting = [(e.exp, t) for e in leftover for t in e.trials]
            self._note_queue_state(leftover, now)

    def _fingerprint_grouped(self, entries):
        """Legacy-path dispatch ordering (ISSUE 7 + ISSUE 8): units whose
        trials compile to the same program (equal semantic dispatch-group
        key, analysis/program.py) dispatch consecutively, so the first
        unit's trace/compile warms the jit and persistent-XLA caches for
        the rest; with the AOT compile service attached, groups whose
        executable is already WARM in the registry dispatch before cold
        groups (one dict lookup per group). Stable: groups appear at their
        first member's arrival position, members keep arrival order, and
        units with no key (analysis off, command template, no probe) are
        singleton groups — with no keys (or no compile service) the walk
        is the identity, preserving FIFO exactly. Caller holds the
        scheduler lock."""
        from ..analysis import program as semantic

        cs = self._cs()
        first_pos: Dict[Any, int] = {}
        rank: Dict[Any, int] = {}
        keyed = []
        for i, e in enumerate(entries):
            try:
                key = semantic.dispatch_group_key(e.exp.spec, e.trials[0])
            except Exception:
                key = None  # advisory: ordering must never break dispatch
            gid = ("solo", i) if key is None else ("fp", key)
            if gid not in first_pos:
                first_pos[gid] = i
                warm = False
                if cs is not None and key is not None:
                    from ..compilesvc.service import STATE_WARM

                    warm = cs.state_for_key(key) == STATE_WARM
                rank[gid] = 0 if warm else 1
            keyed.append((rank[gid], first_pos[gid], i, e))
        keyed.sort(key=lambda t: (t[0], t[1], t[2]))
        return [e for _, _, _, e in keyed]

    def _gate_hold(self, entry, now: float) -> bool:
        """Compile-gated dispatch: True to hold a ready unit because the
        service is still compiling its program (state pending/compiling)
        and the hold is younger than compile_gate_seconds. The consult is a
        dict lookup — dispatch never blocks inline on XLA; when the gate
        expires (or the compile fails) the unit dispatches and compiles
        inline exactly as before. Caller holds the scheduler lock."""
        cs = self._cs()
        if cs is None or self.compile_gate_seconds <= 0:
            return False
        from ..analysis import program as semantic
        from ..compilesvc.service import STATE_COMPILING, STATE_PENDING

        try:
            key = semantic.dispatch_group_key(entry.exp.spec, entry.trials[0])
        except Exception:
            key = None
        if key is None:
            return False
        state = cs.state_for_key(key)
        if state not in (STATE_PENDING, STATE_COMPILING):
            self._gate_since.pop(key, None)  # warm/failed/unknown: no hold
            return False
        since = self._gate_since.setdefault(key, now)
        remaining = self.compile_gate_seconds - (now - since)
        if remaining <= 0:
            return False  # expired: inline-compile fallback (never re-held
            # for this group until its state leaves pending/compiling)
        for t in entry.trials:
            # span bookkeeping: the queue_wait span of a gated trial gets
            # compileGated/compileGateSeconds stamped at dispatch
            self._gate_held.setdefault(t.name, since)
        if not self._gate_timer_live:
            # one wake timer per hold window so an expired gate re-runs the
            # dispatch pass even if no compile transition fires
            self._gate_timer_live = True
            timer = threading.Timer(min(remaining, 1.0) + 0.02, self._gate_wake)
            timer.daemon = True
            timer.start()
        return True

    def _gate_wake(self) -> None:
        with self._lock:
            self._gate_timer_live = False
        if not self._shutdown.is_set():
            self._dispatch()

    def _start_unit(self, entry, devices) -> None:
        """Spawn the worker thread for one dispatch unit (solo or pack) and
        register its running-unit record; caller holds the scheduler lock."""
        from .fairshare import RunningUnit, priority_of, uses_fairshare

        exp, members = entry.exp, entry.trials
        n = len(devices)
        if self.journal is not None:
            # one intent per dispatch unit: replay (and `katib-tpu recover`)
            # can see which trials shared a gang when the crash hit
            self.journal.append(
                "dispatch", exp.name,
                trials=[t.name for t in members], devices=n,
            )
        if n < entry.requested:
            for t in members:
                self._devices_clamped(exp, t, entry.requested, n)
        for t in members:
            self._clear_enqueue(t.name, exp.name)
        self._usage[exp.name] = self._usage.get(exp.name, 0) + n
        template = exp.spec.trial_template
        if len(members) == 1:
            trial = members[0]
            handle = TrialExecution()
            handles = [handle]
            self._handles[trial.name] = handle
            th = threading.Thread(
                target=self._run_trial,
                args=(exp, trial, devices, handle),
                name=f"trial-{trial.name}",
                daemon=True,
            )
        else:
            handles = [TrialExecution() for _ in members]
            for t, h in zip(members, handles):
                self._handles[t.name] = h
            self._record_pack_formed(exp, members)
            th = threading.Thread(
                target=self._run_pack,
                args=(exp, members, devices, handles),
                name=f"trial-pack-{members[0].name}",
                daemon=True,
            )
        self._unit_devices[entry.key] = list(devices)
        self._running[entry.key] = RunningUnit(
            key=entry.key,
            experiment=exp.name,
            trial_names=[t.name for t in members],
            n_devices=n,
            priority=priority_of(exp),
            # preemption is cooperative through ctx.report(): only
            # in-process single-host units can checkpoint-and-yield
            preemptible=template.command is None and template.resources.num_hosts <= 1,
            started=time.time(),
            fairshare=uses_fairshare(exp),
            handles=handles,
        )
        self._threads.append(th)
        th.start()

    def _plan_preemption(self, entry, free: int) -> None:
        """Ask the policy for a victim set that unblocks ``entry`` and
        signal it: lowest priority first, most-recent checkpoint first.
        Victims checkpoint-and-exit cooperatively at their next report; a
        victim that ignores the signal past the grace window is killed (it
        still requeues, resuming from its last checkpoint, if any). Caller
        holds the scheduler lock."""
        victims = self._policy.select_victims(
            entry.needed,
            free,
            entry.priority,
            list(self._running.values()),
            lambda t: self._last_checkpoint.get(t, 0.0),
        )
        if not victims:
            return
        # preemption is actively clearing chips for this gang — earmark the
        # currently-free chips too, so backfill can't take what the victims
        # are about to deliver
        self._head_credits = max(self._head_credits, min(entry.needed, free))
        for u in victims:
            u.preempt_signaled = True
            self._preempting.update(u.trial_names)
            for h in u.handles:
                h.preempt()
            log.info(
                "preempting %s (%d device(s), priority %d) for %s "
                "(%d device(s), priority %d)",
                ",".join(u.trial_names), u.n_devices, u.priority,
                entry.key, entry.needed, entry.priority,
            )
            if self.preemption_grace_seconds:
                timer = threading.Timer(
                    self.preemption_grace_seconds,
                    lambda hs=list(u.handles): [h.kill() for h in hs],
                )
                timer.daemon = True
                timer.start()

    def _note_queue_state(self, leftover, now: float) -> None:
        """Per-dispatch-pass queue observability: TrialQueueStalled warnings
        for trials pending past the threshold, plus the katib_queue_depth /
        katib_queue_wait_seconds / katib_fairshare_deficit gauges. Caller
        holds the scheduler lock."""
        depth: Dict[str, int] = {}
        oldest: Dict[str, float] = {}
        for e in leftover:
            for t in e.trials:
                depth[e.exp.name] = depth.get(e.exp.name, 0) + 1
                wait = max(now - self._enqueued_at.get(t.name, now), 0.0)
                oldest[e.exp.name] = max(oldest.get(e.exp.name, 0.0), wait)
                if (
                    self.queue_stall_seconds
                    and wait > self.queue_stall_seconds
                    and t.name not in self._stall_emitted
                ):
                    self._stall_emitted.add(t.name)
                    log.warning(
                        "trial %s has been pending %.0fs for %d device(s) "
                        "(free: %d) — head-of-line blocking, quota, or "
                        "starvation", t.name, wait, e.needed,
                        self.allocator.free_count,
                    )
                    if self.recorder is not None:
                        self.recorder.event(
                            e.exp.name, "Trial", t.name, "TrialQueueStalled",
                            f"pending for {wait:.0f}s waiting for {e.needed} "
                            f"device(s) (free: {self.allocator.free_count}); "
                            "see /api/queue for queue state",
                            warning=True,
                        )
        if self.metrics_registry is not None:
            names = set(depth) | self._gauged_experiments
            deficits = self._policy.deficits(sorted({e.exp.name for e in leftover}))
            for name in names:
                self.metrics_registry.set_gauge(
                    "katib_queue_depth", float(depth.get(name, 0)), experiment=name
                )
                self.metrics_registry.set_gauge(
                    "katib_queue_wait_seconds",
                    round(oldest.get(name, 0.0), 3),
                    experiment=name,
                )
                self.metrics_registry.set_gauge(
                    "katib_fairshare_deficit",
                    round(deficits.get(name, 0.0), 3),
                    experiment=name,
                )
            self._gauged_experiments = set(depth)

    def _devices_clamped(
        self, exp: Experiment, trial: Trial, requested: int, granted: int
    ) -> None:
        """An allocation the machine cannot satisfy is clamped rather than
        wedged forever — but silently shrinking a gang hides undersized
        hardware from the operator, so make it visible."""
        log.warning(
            "trial %s requested %d devices but the machine has %d; "
            "allocation clamped", trial.name, requested, granted,
        )
        if self.recorder is not None:
            self.recorder.event(
                exp.name, "Trial", trial.name, "TrialDevicesClamped",
                f"requested {requested} devices, machine total is {granted}; "
                "allocation clamped to the machine",
                warning=True,
            )

    def _record_pack_formed(self, exp: Experiment, members: Sequence[Trial]) -> None:
        from .packing import pack_capacity

        k = max(pack_capacity(exp), 1)
        tr = self._tr()
        if tr is not None:
            # instantaneous stage marker in each member's trace: the moment
            # pack formation merged it into a shared dispatch unit
            now = time.time()
            for t in members:
                mroot = tr.trial_root(exp.name, t.name)
                if mroot is not None:
                    tr.record_span(
                        "pack_formation", exp.name, mroot.trace_id,
                        mroot.span_id, start=now, end=now,
                        members=len(members), capacity=k,
                    )
        if self.metrics_registry is not None:
            self.metrics_registry.inc("katib_pack_formed_total", experiment=exp.name)
            self.metrics_registry.inc(
                "katib_trial_packed_total", value=float(len(members)),
                experiment=exp.name,
            )
            self.metrics_registry.set_gauge(
                "katib_pack_occupancy", len(members) / k, experiment=exp.name
            )
        if self.recorder is not None:
            self.recorder.event(
                exp.name, "Trial", members[0].name, "PackFormed",
                f"packed {len(members)}/{k} trials into one program: "
                + ", ".join(t.name for t in members),
            )

    def _run_trial(self, exp: Experiment, trial: Trial, devices, handle: TrialExecution) -> None:
        from ..tracing import pop_log_context, push_log_context

        restarted = False
        requeued = False
        started = time.time()
        timer = None
        ctx: Optional[TrialContext] = None
        abandoned: Optional[threading.Thread] = None
        timed_out = threading.Event()
        tr = self._tr()
        tm = self._tm()
        root = tr.trial_root(exp.name, trial.name) if tr is not None else None
        run_span = exec_span = None
        if root is not None:
            run_span = tr.start_span(
                "run", exp.name, root.trace_id, root.span_id,
                attrs={"devices": len(devices)},
            )
        if tm is not None:
            # resource sampling for this run stint (telemetry.py): starts as
            # in-process attribution; the executor re-points it at the child
            # pids via ctx.on_subprocess when the trial forks
            tm.register_trial(exp.name, trial.name)
        log_token = push_log_context(
            experiment=exp.name, trial=trial.name,
            trace_id=root.trace_id if root is not None else "",
        )
        try:
            trial.set_condition(TrialCondition.RUNNING, "TrialRunning", "Trial is running")
            self.state.update_trial(trial)

            if self.trial_timeout:
                def _deadline():
                    timed_out.set()
                    handle.kill()

                timer = threading.Timer(self.trial_timeout, _deadline)
                timer.daemon = True
                timer.start()

            setup_span = None
            if run_span is not None:
                setup_span = tr.start_span(
                    "executor_setup", exp.name, run_span.trace_id, run_span.span_id
                )
            ctx = self._build_context(exp, trial, devices, handle)
            spec = exp.spec
            if (
                spec.trial_template.resources.num_hosts > 1
                and spec.trial_template.function is None
            ):
                # gang of worker processes forming one jax.distributed system
                executor = self._multihost
            elif spec.trial_template.command is not None:
                executor = self._subprocess
            else:
                executor = self._in_process
            if run_span is not None:
                tr.end_span(setup_span, executor=type(executor).__name__)
                exec_span = tr.start_span(
                    "execute", exp.name, run_span.trace_id, run_span.span_id,
                    attrs={"executor": type(executor).__name__},
                )
                # runtime-side spans (compile boundary, steps, checkpoint,
                # flush barriers) hang off the execute span
                ctx.bind_trace(tr, exp.name, run_span.trace_id, exec_span.span_id)
            result, abandoned = self._execute_bounded(
                executor, exp, trial, ctx, handle, timed_out
            )
            if exec_span is not None:
                tr.end_span(exec_span, outcome=result.outcome.value)

            if timed_out.is_set() and result.outcome == TrialOutcome.KILLED:
                # deadline exceeded counts against maxFailedTrialCount
                result = ExecutionResult(
                    TrialOutcome.FAILED,
                    f"trial exceeded timeout of {self.trial_timeout}s",
                )
            result = self._convert_backend_loss(trial, result, devices)
            # Preemption first: a preempted trial is neither classified nor
            # finalized — it requeues as resumable and its next run's fold
            # continues the same observation log (checkpoint resume) or a
            # clean one (no checkpoint).
            if self._preempt_applies(trial, result):
                preempt_start = time.time()
                requeued = self._requeue_preempted(exp, trial)
                if requeued and run_span is not None:
                    tr.record_span(
                        "preempted", exp.name, run_span.trace_id, run_span.span_id,
                        start=preempt_start, end=time.time(),
                        resumable=trial.name in self._last_checkpoint,
                    )
            if not requeued:
                # Classify (observation fold + success/failure conditions)
                # BEFORE the restart decision: a non-zero-exit trial a
                # success_condition rescues must not burn max_trial_restarts
                # attempts, and an rc=0 trial a failure_condition flips to
                # Failed must be retried like any other failure.
                fin_span = None
                if run_span is not None:
                    fin_span = tr.start_span(
                        "finalize", exp.name, run_span.trace_id, run_span.span_id
                    )
                result, observation = self._classify(exp, trial, result)
                paused = False
                mf = self._mf()
                if mf is not None and result.outcome == TrialOutcome.COMPLETED:
                    # rung-boundary consult (controller/multifidelity.py): a
                    # multi-fidelity trial that completed its assigned budget
                    # is PAUSED — checkpoint + observations intact — instead
                    # of finalized; a promotion resubmits it at the next
                    # fidelity. Non-asha experiments return False untouched.
                    try:
                        paused = mf.on_rung_boundary(exp, trial, observation, self)
                    except Exception:
                        log.warning("rung boundary consult failed", exc_info=True)
                if not paused:
                    restarted = self._maybe_restart(exp, trial, result)
                    if not restarted:
                        self._finalize(exp, trial, result, observation)
                if fin_span is not None:
                    tr.end_span(fin_span, restarted=restarted, rung_paused=paused)
        except Exception:
            trial.set_condition(TrialCondition.FAILED, "TrialFailed", traceback.format_exc(limit=5))
            self.state.update_trial(trial)
        finally:
            if timer is not None:
                timer.cancel()
            if tm is not None:
                # the stint's resource summary lands on the trial root span
                # BEFORE it is ended/persisted below
                self._telemetry_finalize(tm, trial.name, root)
            if (
                self.step_stats is not None
                and ctx is not None
                and ctx.step_clock is not None
            ):
                # stint rows + RetraceStorm/StepTimeRegression + rollups.
                # Requeued/restarted stints skip persistence: their rows
                # would be truncated to the last checkpoint on resume (or
                # the log dropped on restart) — the next stint re-measures.
                self.step_stats.finalize_stint(
                    exp, trial.name, ctx.step_clock, self.obs_store,
                    n_devices=len(devices),
                    write_rows=not (requeued or restarted),
                )
            if run_span is not None:
                tr.end_span(exec_span)  # no-op unless an exception skipped it
                tr.end_span(run_span, requeued=requeued, restarted=restarted)
            if tr is not None and not requeued and not restarted:
                self._trace_end_trial(exp.name, trial)
            pop_log_context(log_token)
            with self._lock:
                self._running.pop(trial.name, None)
                self._unit_devices.pop(trial.name, None)
                if not requeued:
                    self._preempting.discard(trial.name)
            if abandoned is not None and abandoned.is_alive():
                # An abandoned in-process trial may still be running JAX work
                # on these chips — quarantine them (don't hand them to the
                # next trial) until the zombie thread actually exits.
                self._quarantine(trial.name, devices, abandoned, exp, started)
            else:
                self._release_allocation(exp, devices, started)
            with self._lock:
                self._handles.pop(trial.name, None)
                if not restarted and not requeued:
                    self._checkpoint_dirs.pop(trial.name, None)
                    self._restarts.pop(trial.name, None)
                    self._last_checkpoint.pop(trial.name, None)
            self.events.put(TrialEvent(exp.name, trial.name, trial.condition))
            self._dispatch()

    def _report_heartbeat_hook(
        self, names: Sequence[str], holder: str
    ) -> Optional[Callable[[], None]]:
        """Combined per-report liveness hook: telemetry watchdog heartbeats
        for every member plus the device plane's lease heartbeat for the
        unit (which is also where scheduled chaos faults fire). None when
        both subsystems are off, so ctx.report pays one check."""
        tm, dp = self._tm(), self._dp()
        if tm is None and dp is None:
            return None

        def hook(_tm=tm, _dp=dp, _names=tuple(names), _holder=holder):
            if _tm is not None:
                for n in _names:
                    _tm.heartbeat(n)
            if _dp is not None:
                _dp.heartbeat(_holder)

        return hook

    def _telemetry_finalize(self, tm, trial_name: str, root) -> None:
        """Close one trial's telemetry stint: unregister (persists its
        sample ring) and stamp the peak-RSS / peak-HBM / mean-CPU summary
        onto the trial's root span so the trace answers cost, not just
        time. ``root`` is None when tracing is off."""
        summary = tm.unregister_trial(trial_name)
        if summary and root is not None:
            root.set(
                peak_rss_bytes=summary["peakRssBytes"],
                peak_hbm_bytes=summary["peakHbmBytes"],
                mean_cpu_percent=summary["meanCpuPercent"],
            )

    def _run_pack(
        self,
        exp: Experiment,
        trials: List[Trial],
        devices,
        handles: List[TrialExecution],
    ) -> None:
        """Run one formed pack to completion: K trials, one gang allocation,
        one PackedTrialExecutor call, then per-trial condition fan-out —
        each member is classified/finalized independently, exactly like K
        solo trials would be."""
        from ..tracing import pop_log_context, push_log_context
        from .packing import PACK_LABEL

        timer = None
        started = time.time()
        requeued: set = set()
        ctx = None
        abandoned: Optional[threading.Thread] = None
        timed_out = threading.Event()
        pack_id = f"{trials[0].name}x{len(trials)}"
        tr = self._tr()
        tm = self._tm()
        if tm is not None:
            for t in trials:
                tm.register_trial(exp.name, t.name)  # in-process: shared attribution
        # one gang-level trace per pack (root `pack` span + K member child
        # spans); each member's own trial trace gets a `run` span linking to
        # it, so both the per-trial and the shared-program views connect
        gang = (
            tr.begin_gang(exp.name, pack_id, [t.name for t in trials])
            if tr is not None
            else None
        )
        member_runs: Dict[str, Any] = {}
        if gang is not None:
            for t in trials:
                mroot = tr.trial_root(exp.name, t.name)
                if mroot is not None:
                    member_runs[t.name] = tr.start_span(
                        "run", exp.name, mroot.trace_id, mroot.span_id,
                        attrs={"pack": pack_id, "packTraceId": gang.trace_id},
                    )
        log_token = push_log_context(
            experiment=exp.name, trial=pack_id,
            trace_id=gang.trace_id if gang is not None else "",
        )
        try:
            for t in trials:
                t.labels[PACK_LABEL] = pack_id
                t.set_condition(
                    TrialCondition.RUNNING, "TrialRunning",
                    f"Trial is running (packed, {len(trials)} members)",
                )
                self.state.update_trial(t)

            if self.trial_timeout:
                def _deadline():
                    timed_out.set()
                    for h in handles:
                        h.kill()

                timer = threading.Timer(self.trial_timeout, _deadline)
                timer.daemon = True
                timer.start()

            ctx = self._build_pack_context(exp, trials, devices, handles)
            # one demuxed report() heartbeats every member — the watchdog
            # sees the pack's shared step loop, not K separate clocks — and
            # ticks the gang's device lease in the plane
            hook = self._report_heartbeat_hook(
                [t.name for t in trials], trials[0].name
            )
            if hook is not None:
                ctx.on_report = hook
            if gang is not None:
                # shared compiled program: compile/steps/flush spans land in
                # the gang trace under the pack root
                ctx.bind_trace(tr, exp.name, gang.trace_id, gang.root.span_id)
            executor = self._pack_executor(exp, trials)
            results, abandoned = self._execute_pack_bounded(
                executor, exp, trials, ctx, handles, timed_out
            )
            results = self._convert_pack_backend_loss(
                pack_id, trials, results, devices
            )
            for trial, result in zip(trials, results):
                if timed_out.is_set() and result.outcome == TrialOutcome.KILLED:
                    result = ExecutionResult(
                        TrialOutcome.FAILED,
                        f"trial exceeded timeout of {self.trial_timeout}s",
                    )
                # a pack preempts as one unit, but members requeue
                # individually — they re-pack (or run solo) on redispatch
                if self._preempt_applies(trial, result):
                    if self._requeue_preempted(exp, trial):
                        requeued.add(trial.name)
                        if gang is not None:
                            tr.end_span(
                                gang.members.get(trial.name), outcome="preempted"
                            )
                            tr.end_span(
                                member_runs.get(trial.name), requeued=True
                            )
                        continue
                result, observation = self._classify(exp, trial, result)
                mf = self._mf()
                if mf is not None and result.outcome == TrialOutcome.COMPLETED:
                    # packed bottom rungs hit the same boundary consult as
                    # solo trials: each member pauses (or promotes)
                    # independently when the shared program completes
                    try:
                        rung_paused = mf.on_rung_boundary(
                            exp, trial, observation, self
                        )
                    except Exception:
                        rung_paused = False
                        log.warning("rung boundary consult failed", exc_info=True)
                    if rung_paused:
                        with self._lock:
                            self._checkpoint_dirs.pop(trial.name, None)
                            self._restarts.pop(trial.name, None)
                            self._last_checkpoint.pop(trial.name, None)
                        if gang is not None:
                            tr.end_span(
                                gang.members.get(trial.name), outcome="rung-paused"
                            )
                            tr.end_span(
                                member_runs.get(trial.name), rung_paused=True
                            )
                        continue
                restarted = self._maybe_restart(exp, trial, result)
                if not restarted:
                    self._finalize(exp, trial, result, observation)
                    with self._lock:
                        self._checkpoint_dirs.pop(trial.name, None)
                        self._restarts.pop(trial.name, None)
                        self._last_checkpoint.pop(trial.name, None)
                if gang is not None:
                    tr.end_span(
                        gang.members.get(trial.name), outcome=result.outcome.value
                    )
                    tr.end_span(member_runs.get(trial.name), restarted=restarted)
        except Exception:
            tb = traceback.format_exc(limit=5)
            for t in trials:
                if not t.is_terminal:
                    t.set_condition(TrialCondition.FAILED, "TrialFailed", tb)
                    self.state.update_trial(t)
        finally:
            if timer is not None:
                timer.cancel()
            if tm is not None:
                for t in trials:
                    self._telemetry_finalize(
                        tm, t.name,
                        tr.trial_root(exp.name, t.name) if tr is not None else None,
                    )
            if (
                self.step_stats is not None
                and ctx is not None
                and getattr(ctx, "_step_clocks", None) is not None
            ):
                # per-member stint rows + detectors, then the gang-level
                # straggler check; requeued members skip persistence (their
                # rows truncate to the last checkpoint on resume)
                self.step_stats.finalize_pack(
                    exp, [t.name for t in trials], ctx._step_clocks,
                    self.obs_store, n_devices=len(devices),
                    requeued=[t.name in requeued for t in trials],
                )
            if gang is not None:
                for t in trials:
                    tr.end_span(gang.members.get(t.name))
                    tr.end_span(member_runs.get(t.name))
                tr.end_span(gang.root)
                for t in trials:
                    if t.name not in requeued:
                        self._trace_end_trial(exp.name, t)
            pop_log_context(log_token)
            with self._lock:
                self._running.pop(trials[0].name, None)
                self._unit_devices.pop(trials[0].name, None)
                for t in trials:
                    if t.name not in requeued:
                        self._preempting.discard(t.name)
            if abandoned is not None and abandoned.is_alive():
                self._quarantine(pack_id, devices, abandoned, exp, started)
            else:
                self._release_allocation(exp, devices, started)
            with self._lock:
                for t in trials:
                    self._handles.pop(t.name, None)
            for t in trials:
                self.events.put(TrialEvent(exp.name, t.name, t.condition))
            self._dispatch()

    def _pack_executor(self, exp: Experiment, trials: List[Trial]):
        """Executor for one formed pack: an opted-in fused population sweep
        (every member carries the fused label and the template exposes a
        population_program probe) runs through the FusedPopulationExecutor
        — the whole sweep in compiled lax.scan chunks; anything else keeps
        the PackedTrialExecutor path unchanged."""
        from ..runtime import population as pop
        from .packing import FusedPopulationExecutor, PackedTrialExecutor

        if (
            self.fused_population
            and all(pop.FUSED_LABEL in t.labels for t in trials)
            and pop.fused_applicable(exp.spec) is None
        ):
            return FusedPopulationExecutor(
                self.obs_store,
                chunk_generations=self.population_chunk_generations,
                stream=self.population_stream,
                compile_service=self._cs(),
                metrics=self.metrics_registry,
            )
        return PackedTrialExecutor(self.obs_store)

    def _execute_pack_bounded(
        self,
        executor,
        exp: Experiment,
        trials: List[Trial],
        ctx,
        handles: List[TrialExecution],
        timed_out: threading.Event,
    ) -> "tuple[List[ExecutionResult], Optional[threading.Thread]]":
        """Pack counterpart of _execute_bounded. Individual member kills are
        cooperative (frozen at the next ctx.report); the grace/abandon
        machinery engages only when EVERY member was asked to stop (timeout
        or shutdown) and the shared program still refuses to exit — there is
        one program, so there is one thread to abandon."""
        from ..tracing import push_log_context

        box: Dict[str, Any] = {}

        def _exec():
            push_log_context(
                experiment=exp.name, trial=f"{trials[0].name}x{len(trials)}"
            )
            try:
                box["results"] = executor.execute(exp, trials, ctx, handles)
            except BaseException:
                box["error"] = traceback.format_exc(limit=5)

        worker = threading.Thread(
            target=_exec, name=f"pack-exec-{trials[0].name}", daemon=True
        )
        worker.start()
        abandon_at = None
        while worker.is_alive():
            worker.join(timeout=0.2)
            if abandon_at is None and all(h.kill_requested for h in handles):
                abandon_at = time.time() + self.KILL_GRACE_SECONDS
            if abandon_at is not None and time.time() > abandon_at and worker.is_alive():
                if timed_out.is_set():
                    outcome, reason = (
                        TrialOutcome.FAILED,
                        f"trial exceeded timeout of {self.trial_timeout}s",
                    )
                else:
                    outcome, reason = TrialOutcome.KILLED, "kill requested"
                msg = (
                    f"{reason}; pack did not stop within "
                    f"{self.KILL_GRACE_SECONDS}s grace, abandoned"
                )
                return [ExecutionResult(outcome, msg) for _ in trials], worker
        if "error" in box:
            return (
                [ExecutionResult(TrialOutcome.FAILED, box["error"]) for _ in trials],
                None,
            )
        return box["results"], None

    def _build_pack_context(
        self,
        exp: Experiment,
        trials: List[Trial],
        devices,
        handles: List[TrialExecution],
    ):
        """Batched analogue of _build_context: per-member reporters (with
        raise_on_stop=False — stopping is masking, not unwinding, and the
        kill check belongs to the packed context so one member's kill can't
        unwind the shared program), stacked assignments, and per-member
        workdir/checkpoint-dir lists."""
        from ..runtime.packed import PackedTrialContext
        from .packing import stack_assignments

        spec = exp.spec
        reporters = []
        for t in trials:
            monitor = None
            if t.early_stopping_rules:
                monitor = EarlyStoppingMonitor(
                    t.early_stopping_rules,
                    spec.objective.objective_metric_name,
                    spec.objective.type,
                )
            reporters.append(
                MetricsReporter(
                    store=self.obs_store,
                    trial_name=t.name,
                    monitor=monitor,
                    raise_on_stop=False,
                )
            )
        workdirs: List[Optional[str]] = []
        for t in trials:
            workdir = None
            if self.workdir_root:
                import os

                workdir = os.path.join(self.workdir_root, exp.name, t.name)
                os.makedirs(workdir, exist_ok=True)
            workdirs.append(workdir)
        ctx = PackedTrialContext(
            trial_names=[t.name for t in trials],
            experiment_name=exp.name,
            assignments=stack_assignments(trials),
            reporters=reporters,
            kill_events=[h.kill_event for h in handles],
            workdirs=workdirs,
            checkpoint_dirs=[self._checkpoint_dirs.get(t.name) for t in trials],
            member_labels=[dict(t.labels) for t in trials],
            devices=list(devices),
            topology=spec.trial_template.resources.topology,
            preempt_events=[h.preempt_event for h in handles],
            # a fused chunk checkpoint covers EVERY member: stamp them all,
            # so preempted members requeue as resumable (logs kept)
            on_checkpoint=lambda step, _names=[t.name for t in trials]: [
                self._note_checkpoint(n) for n in _names
            ],
        )
        if self.step_stats is not None:
            # one clock per member: the demux marks each active member's
            # clock per report; fused sweeps time chunks instead
            # (note_step_seconds) and the member index keys the straggler
            # injection seam
            ctx._step_clocks = [
                self.step_stats.clock_for(member_index=i)
                for i in range(len(trials))
            ]
        return ctx

    KILL_GRACE_SECONDS = 30.0

    def _execute_bounded(
        self, executor, exp: Experiment, trial: Trial, ctx, handle: TrialExecution,
        timed_out: threading.Event,
    ) -> "tuple[ExecutionResult, Optional[threading.Thread]]":
        """Run the executor on a worker thread so a kill/timeout cannot leak
        the gang allocation. Subprocess trials die on SIGTERM; in-process
        trials unwind cooperatively (TrialKilled raised at their next
        ctx.report()). A function that never reports and never returns is
        abandoned after a grace period — its daemon thread keeps running (a
        Python thread can't be force-killed) and is returned to the caller so
        the devices it may still be using get quarantined, not reissued."""
        from ..tracing import push_log_context

        box: Dict[str, Any] = {}

        def _exec():
            push_log_context(experiment=exp.name, trial=trial.name)
            try:
                box["result"] = executor.execute(exp, trial, ctx, handle)
            except BaseException:
                box["error"] = traceback.format_exc(limit=5)

        worker = threading.Thread(
            target=_exec, name=f"trial-exec-{trial.name}", daemon=True
        )
        worker.start()
        abandon_at = None
        while worker.is_alive():
            worker.join(timeout=0.2)
            if handle.kill_requested and abandon_at is None:
                abandon_at = time.time() + self.KILL_GRACE_SECONDS
            if abandon_at is not None and time.time() > abandon_at and worker.is_alive():
                reason = (
                    f"trial exceeded timeout of {self.trial_timeout}s"
                    if timed_out.is_set()
                    else "kill requested"
                )
                return ExecutionResult(
                    TrialOutcome.FAILED if timed_out.is_set() else TrialOutcome.KILLED,
                    f"{reason}; trial did not stop within "
                    f"{self.KILL_GRACE_SECONDS}s grace, abandoned",
                ), worker
        if "error" in box:
            return ExecutionResult(TrialOutcome.FAILED, box["error"]), None
        return box["result"], None

    def _quarantine(
        self,
        trial_name: str,
        devices: Sequence[Any],
        worker: threading.Thread,
        exp: Experiment,
        started: float,
    ) -> None:
        """Hold the gang allocation of an abandoned (zombie) trial until its
        worker thread actually exits, then release and re-dispatch. The
        zombie keeps burning the chips, so the experiment stays charged (and
        quota-attributed) until the actual release.

        With the device plane attached the hold is a ZOMBIE LEASE, not a
        bare counter: past runtime.device_lease_seconds the plane reclaims
        the chips into the pool (DeviceLeaseRevoked) even if the zombie
        thread never exits — the pre-plane ``_quarantined`` counter counted
        these devices forever without ever returning them (the ISSUE 12
        leak). The late-exiting zombie's release is then a no-op."""
        dp = self._dp()
        if dp is not None:
            dp.mark_zombie(devices, holder=trial_name)
        with self._lock:
            self._quarantined += len(devices)
        log.warning(
            "quarantining %d device(s) of abandoned trial %s until its "
            "worker thread exits", len(devices), trial_name,
        )

        def _reap():
            worker.join()
            with self._lock:
                self._quarantined -= len(devices)
            log.warning(
                "abandoned trial %s finally exited; releasing %d quarantined "
                "device(s)", trial_name, len(devices),
            )
            self._release_allocation(exp, devices, started)
            self._dispatch()

        threading.Thread(
            target=_reap, daemon=True, name=f"reap-{trial_name}"
        ).start()

    def _release_allocation(self, exp: Experiment, devices: Sequence[Any], started: float) -> None:
        """The one release path for gang allocations: fair-share usage is
        charged (device-seconds / weight), the experiment's quota attribution
        drops, and chips released while a blocked head holds the reservation
        accrue to its backfill-proof credits."""
        from .fairshare import weight_of

        elapsed = max(time.time() - started, 0.0)
        with self._lock:
            self._usage[exp.name] = max(0, self._usage.get(exp.name, 0) - len(devices))
            if self._head_key is not None:
                self._head_credits += len(devices)
        self._policy.charge(exp.name, len(devices) * elapsed, weight_of(exp))
        mf = self._mf()
        if (
            mf is not None
            and self.metrics_registry is not None
            and mf.applies(exp.spec)
        ):
            # per-stint device-seconds attribution: every rung stint of a
            # multi-fidelity sweep charges its gang here, so the bench's
            # ASHA-vs-flat comparison reads straight off /metrics
            self.metrics_registry.inc(
                "katib_multifidelity_device_seconds",
                value=round(len(devices) * elapsed, 6),
                experiment=exp.name,
            )
        if self.step_stats is not None:
            # objective-per-device-second rollup (ISSUE 20 satellite): every
            # gang release charges its device-seconds, multi-fidelity or not
            self.step_stats.charge_device_seconds(exp.name, len(devices) * elapsed)
        self.allocator.release(devices)

    def _note_checkpoint(self, trial_name: str) -> None:
        """ctx.checkpoint_store() save hook: victim selection prefers
        recently-checkpointed trials, and a preempted trial resumes (keeps
        its observation log) only if it checkpointed at all."""
        with self._lock:
            self._last_checkpoint[trial_name] = time.time()

    def _convert_backend_loss(
        self, trial: Trial, result: ExecutionResult, devices: Sequence[Any]
    ) -> ExecutionResult:
        """Device-loss-as-preemption (controller/deviceplane.py): a FAILED
        result whose traceback carries a backend-death signature
        (XlaRuntimeError and friends) means the DEVICES died, not the
        trial's code. The gang's devices are marked lost in the plane (they
        never return to the pool — and their disappearance can trigger
        failover), and the result converts to PREEMPTED so the standard
        requeue machinery resumes the trial on surviving devices from its
        last checkpoint (or re-runs it clean). No plane, or no signature
        match: the result passes through untouched."""
        from . import deviceplane

        dp = self._dp()
        if (
            dp is None
            or result.outcome != TrialOutcome.FAILED
            or not deviceplane.is_backend_loss(result.message)
            or not dp.report_executor_failure(trial.name, devices)
        ):
            return result
        with self._lock:
            self._preempting.add(trial.name)
        log.warning(
            "trial %s failed with a backend-death signature; converting to "
            "a device-loss preemption", trial.name,
        )
        return ExecutionResult(
            TrialOutcome.PREEMPTED,
            "backend error under the program (device loss); converted to a "
            "checkpoint-preemption: " + (result.message or "").strip()[-200:],
        )

    def _convert_pack_backend_loss(
        self,
        pack_id: str,
        trials: List[Trial],
        results: List[ExecutionResult],
        devices: Sequence[Any],
    ) -> List[ExecutionResult]:
        """Pack counterpart of _convert_backend_loss: one shared program,
        so one backend-death signature marks the whole gang's devices lost
        and every member failed by it converts to a preemption (members
        with their own outcome — killed, early-stopped — keep it)."""
        from . import deviceplane

        dp = self._dp()
        if dp is None:
            return results
        struck = [
            i
            for i, r in enumerate(results)
            if r.outcome == TrialOutcome.FAILED
            and deviceplane.is_backend_loss(r.message)
        ]
        if not struck or not dp.report_executor_failure(pack_id, devices):
            return results
        with self._lock:
            self._preempting.update(trials[i].name for i in struck)
        log.warning(
            "pack %s failed with a backend-death signature; converting %d "
            "member(s) to device-loss preemptions", pack_id, len(struck),
        )
        out = list(results)
        for i in struck:
            out[i] = ExecutionResult(
                TrialOutcome.PREEMPTED,
                "backend error under the shared program (device loss); "
                "converted to a checkpoint-preemption: "
                + (results[i].message or "").strip()[-200:],
            )
        return out

    def _preempt_applies(self, trial: Trial, result: ExecutionResult) -> bool:
        """Did this trial end because the fair-share policy preempted it?
        PREEMPTED is the cooperative exit; KILLED covers the grace-window
        escalation. A deliberate kill() or a controller shutdown always wins
        over a pending preemption, and a timeout (FAILED) stays a failure."""
        if self._shutdown.is_set():
            return False
        with self._lock:
            signaled = trial.name in self._preempting
            deliberate = trial.name in self._intentional_kills
        return (
            signaled
            and not deliberate
            and result.outcome in (TrialOutcome.PREEMPTED, TrialOutcome.KILLED)
        )

    def _requeue_preempted(self, exp: Experiment, trial: Trial) -> bool:
        """Requeue a preempted trial as resumable: PENDING again, back of
        the fair-share queue (its lower priority keeps it behind the gang
        that preempted it). With a checkpoint on record the observation log
        is KEPT — the resumed run continues reporting where it stopped, so
        the folded metrics are bit-identical to an unpreempted run; without
        one the re-run starts from scratch and the log is dropped (the same
        invariant as restart requeues)."""
        with self._lock:
            self._preempting.discard(trial.name)
            has_checkpoint = trial.name in self._last_checkpoint
        # the cooperative exit already ran the reporter's flush barrier; this
        # covers the grace-window kill escalation, where the victim's last
        # report predates the preempt signal and may still sit in the buffer
        self.obs_store.flush()
        if not has_checkpoint:
            self.obs_store.delete_observation_log(trial.name)
        trial.set_condition(
            TrialCondition.PENDING,
            "TrialPreempted",
            "preempted by higher-priority work; requeued"
            + (" (resumes from checkpoint)" if has_checkpoint else ""),
        )
        self.state.update_trial(trial)
        if self.metrics_registry is not None:
            self.metrics_registry.inc(
                "katib_trial_preempted_total", experiment=exp.name
            )
        if self.recorder is not None:
            self.recorder.event(
                exp.name, "Trial", trial.name, "TrialPreempted",
                "trial preempted by higher-priority work and requeued"
                + (" (resumes from checkpoint)" if has_checkpoint else ""),
            )
        with self._lock:
            self._stamp_enqueue(exp, trial)
            self._waiting.append((exp, trial))
        return True

    def forget_experiment(self, name: str) -> None:
        """Drop a deleted experiment's fair-share ledger + quota attribution
        so a future namesake starts with a clean share."""
        self._policy.forget(name)
        with self._lock:
            self._usage.pop(name, None)

    def queue_state(self) -> Dict[str, Any]:
        """Observable queue snapshot for /api/queue and the CLI: pending
        trials with priority / wait / fair-share deficit, running units, and
        the device pool."""
        from . import fairshare as fs

        now = time.time()
        dp = self._dp()
        with self._lock:
            waiting = list(self._waiting)
            running = list(self._running.values())
            enq = dict(self._enqueued_at)
            # the plane's count is live (zombie leases leave it when
            # reclaimed); the legacy counter only drops on thread exit
            quarantined = (
                dp.zombie_device_count() if dp is not None else self._quarantined
            )
            usage = dict(self._usage)
        deficits = self._policy.deficits(sorted({exp.name for exp, _ in waiting}))
        pending = []
        for exp, t in waiting:
            enqueued = enq.get(t.name, now)
            prio = fs.priority_of(exp)
            pending.append(
                {
                    "trial": t.name,
                    "experiment": exp.name,
                    "priorityClass": exp.spec.priority_class or "default",
                    "priority": prio,
                    "effectivePriority": round(
                        self._policy.effective_priority(prio, enqueued, now), 3
                    ),
                    "waitSeconds": round(max(now - enqueued, 0.0), 3),
                    "numDevices": max(exp.spec.trial_template.resources.num_devices, 1),
                    "deviceQuota": fs.device_quota_of(exp),
                    "fairShareDeficit": round(deficits.get(exp.name, 0.0), 3),
                }
            )
        pending.sort(key=lambda p: (-p["effectivePriority"], -p["waitSeconds"]))
        devices_view: Dict[str, Any] = {
            "total": self.allocator.total,
            "free": self.allocator.free_count,
            "quarantined": quarantined,
            "usageByExperiment": usage,
        }
        if dp is not None:
            devices_view["backend"] = dp.backend
            devices_view["lostTotal"] = dp.snapshot()["lostTotal"]
        return {
            "devices": devices_view,
            "pending": pending,
            "running": [
                {
                    "unit": u.key,
                    "experiment": u.experiment,
                    "trials": list(u.trial_names),
                    "devices": u.n_devices,
                    "priority": u.priority,
                    "preempting": u.preempt_signaled,
                    "runningSeconds": round(now - u.started, 3),
                }
                for u in running
            ],
        }

    @property
    def quarantined_count(self) -> int:
        dp = self._dp()
        if dp is not None:
            return dp.zombie_device_count()
        with self._lock:
            return self._quarantined

    def _maybe_restart(self, exp: Experiment, trial: Trial, result: ExecutionResult) -> bool:
        """Retry failed trials up to KatibConfig max_trial_restarts times
        (the reference leaves retries to the trial job's backoffLimit)."""
        if result.outcome != TrialOutcome.FAILED or not self.max_trial_restarts:
            return False
        with self._lock:
            attempts = self._restarts.get(trial.name, 0)
            if attempts >= self.max_trial_restarts:
                return False
            self._restarts[trial.name] = attempts + 1
        # drop the failed attempt's metrics so the next attempt's fold (and
        # its success/failure-condition classification) can't mix two
        # executions — same invariant as the requeue path in experiment.py
        self.obs_store.delete_observation_log(trial.name)
        trial.set_condition(
            TrialCondition.PENDING,
            "TrialRestarting",
            f"retry {attempts + 1}/{self.max_trial_restarts}: {result.message}",
        )
        self.state.update_trial(trial)
        with self._lock:
            self._stamp_enqueue(exp, trial)
            self._waiting.append((exp, trial))
        return True

    def _build_context(
        self, exp: Experiment, trial: Trial, devices, handle: Optional[TrialExecution] = None
    ) -> TrialContext:
        spec = exp.spec
        monitor = None
        if trial.early_stopping_rules:
            monitor = EarlyStoppingMonitor(
                trial.early_stopping_rules,
                spec.objective.objective_metric_name,
                spec.objective.type,
            )
        reporter = MetricsReporter(
            store=self.obs_store,
            trial_name=trial.name,
            monitor=monitor,
            kill_event=handle.kill_event if handle is not None else None,
            preempt_event=handle.preempt_event if handle is not None else None,
        )
        workdir = None
        if self.workdir_root:
            import os

            workdir = os.path.join(self.workdir_root, exp.name, trial.name)
            os.makedirs(workdir, exist_ok=True)
        tm = self._tm()
        compiled = None
        cs = self._cs()
        if cs is not None:
            # warm handoff: the AOT-compiled executable for this trial's
            # dispatch group (None when cold/evicted — the trial then
            # compiles inline and the persistent XLA cache still applies)
            try:
                compiled = cs.warm_executable_for(exp.spec, trial)
            except Exception:
                compiled = None
        return TrialContext(
            trial_name=trial.name,
            experiment_name=exp.name,
            assignments=trial.assignments_dict(),
            reporter=reporter,
            workdir=workdir,
            checkpoint_dir=self._checkpoint_dirs.get(trial.name),
            devices=list(devices),
            labels=dict(trial.labels),
            topology=spec.trial_template.resources.topology,
            on_checkpoint=lambda step, _t=trial.name: self._note_checkpoint(_t),
            # telemetry hooks (None when off — ctx.report pays one check):
            # every report is a watchdog heartbeat AND a device-lease
            # heartbeat; subprocess executors re-point /proc sampling at
            # the child pids they spawn
            on_report=self._report_heartbeat_hook([trial.name], trial.name),
            on_subprocess=(
                (lambda pids, _t=trial.name, _tm=tm: _tm.set_pids(_t, pids))
                if tm is not None else None
            ),
            compiled_program=compiled,
            step_clock=(
                self.step_stats.clock_for()
                if self.step_stats is not None else None
            ),
        )

    CONDITION_STDOUT_TAIL = 65536  # bytes of stdout offered to conditions

    def _apply_conditions(
        self, exp: Experiment, result: ExecutionResult, observation
    ) -> ExecutionResult:
        """Trial-defined success/failure predicates over terminal state
        (controller/conditions.py; reference job_util.go:59-120 — failure
        checked first, then success, else the default classification)."""
        template = exp.spec.trial_template
        if not (template.success_condition or template.failure_condition):
            return result
        if result.outcome not in (TrialOutcome.COMPLETED, TrialOutcome.FAILED):
            return result  # killed / early-stopped are controller-initiated
        from .conditions import ConditionError, evaluate_condition

        metrics: Dict[str, float] = {}
        for m in observation.metrics:
            if m.latest != UNAVAILABLE_METRIC_VALUE:
                try:
                    metrics[m.name] = float(m.latest)
                except ValueError:
                    pass
        stdout = ""
        if result.stdout_path:
            try:
                with open(result.stdout_path, "rb") as f:
                    f.seek(0, 2)
                    f.seek(max(0, f.tell() - self.CONDITION_STDOUT_TAIL))
                    stdout = f.read().decode(errors="replace")
            except OSError:
                pass
        state = dict(
            exit_code=result.exit_code,
            outcome=result.outcome.value,
            metrics=metrics,
            stdout=stdout,
        )
        if template.failure_condition:
            try:
                if evaluate_condition(template.failure_condition, **state):
                    return ExecutionResult(
                        TrialOutcome.FAILED,
                        f"failure condition met: {template.failure_condition}",
                        exit_code=result.exit_code,
                        stdout_path=result.stdout_path,
                    )
            except ConditionError as e:
                log.warning("trial failure condition error: %s", e)
        if template.success_condition:
            try:
                met = evaluate_condition(template.success_condition, **state)
            except ConditionError as e:
                met = False
                log.warning("trial success condition error: %s", e)
            if met:
                return ExecutionResult(
                    TrialOutcome.COMPLETED,
                    f"success condition met: {template.success_condition}",
                    exit_code=result.exit_code,
                    stdout_path=result.stdout_path,
                )
            # a finished process produces no further state, so an unmet
            # success condition is terminal failure (job_util.go would keep
            # a job Running awaiting more conditions; see conditions.py)
            msg = f"success condition not met: {template.success_condition}"
            if result.message:
                msg += f" ({result.message})"
            return ExecutionResult(
                TrialOutcome.FAILED,
                msg,
                exit_code=result.exit_code,
                stdout_path=result.stdout_path,
            )
        return result

    def _classify(self, exp: Experiment, trial: Trial, result: ExecutionResult):
        """Fold the observation log and apply trial success/failure
        conditions; returns the (possibly re-classified) result plus the
        folded observation. Runs before the restart decision in _run_trial.
        Answered from the store's incremental fold index (O(metrics));
        stores without one fall back to the full-log rescan."""
        observation = self.obs_store.folded(
            trial.name, exp.spec.objective.all_metric_names()
        )
        trial.observation = observation
        return self._apply_conditions(exp, result, observation), observation

    def _finalize(
        self, exp: Experiment, trial: Trial, result: ExecutionResult, observation
    ) -> None:
        """Terminal-condition bookkeeping for a trial whose result has
        already been classified by _classify (the single classification
        point); mirrors trial_controller_util.go:42-122."""
        spec = exp.spec
        obj_metric = observation.metric(spec.objective.objective_metric_name)
        # "available" deliberately accepts NON-numeric latest values: the
        # reference's darts flow collects a string objective
        # (examples/v1beta1/nas/darts-cpu.yaml objectiveMetricName
        # Best-Genotype, custom filter "(Genotype.*)") and such trials
        # Succeed. Numeric garbage can't arrive via the push SDK
        # (validate_metric_value raises, failing the trial) or the TEXT
        # default filter (numeric regex); a custom filter admitting strings
        # is, as in the reference, the experiment author's declaration that
        # the objective isn't rankable.
        metrics_available = (
            obj_metric is not None and obj_metric.latest != UNAVAILABLE_METRIC_VALUE
        )
        if self.step_stats is not None and metrics_available:
            # best-objective tracking for the per-device-second rollup;
            # non-numeric objectives (custom string collectors) are skipped
            try:
                self.step_stats.note_objective(
                    exp.name, float(obj_metric.latest),
                    spec.objective.type == ObjectiveType.MAXIMIZE,
                )
            except (TypeError, ValueError):
                pass

        if result.outcome == TrialOutcome.EARLY_STOPPED:
            trial.set_condition(
                TrialCondition.EARLY_STOPPED, "TrialEarlyStopped", "Trial is early stopped"
            )
        elif result.outcome == TrialOutcome.KILLED:
            with self._lock:
                deliberate = trial.name in self._intentional_kills
            if self._shutdown.is_set() and not deliberate:
                trial.set_condition(
                    TrialCondition.KILLED, "SchedulerShutdown",
                    "controller shutdown while trial was running",
                )
            else:
                trial.set_condition(TrialCondition.KILLED, "TrialKilled", result.message)
        elif result.outcome == TrialOutcome.FAILED:
            trial.set_condition(TrialCondition.FAILED, "TrialFailed", result.message)
        elif not metrics_available and spec.metrics_collector_spec.collector_kind != CollectorKind.NONE:
            trial.set_condition(
                TrialCondition.METRICS_UNAVAILABLE,
                "MetricsUnavailable",
                "Metrics are not available",
            )
        else:
            trial.set_condition(TrialCondition.SUCCEEDED, "TrialSucceeded", "Trial has succeeded")
        self._record_terminal(exp, trial)

    def _record_terminal(self, exp: Experiment, trial: Trial) -> None:
        """Terminal bookkeeping shared by every path that sets a trial's
        final condition (_finalize and _reuse_duplicate): persist, count,
        record the event, apply retainRun workdir semantics."""
        if self.journal is not None:
            # write-ahead: the journal carries the terminal condition before
            # the state store does, so a crash between the two replays to
            # "finished" instead of re-running a completed trial
            self.journal.append(
                "terminal", exp.name, trial=trial.name,
                condition=trial.condition.value,
                reason=trial.current_reason,
            )
        self.state.update_trial(trial)
        if self.suggestion_prefetch is not None:
            # fire-and-forget: the hook only enqueues a precompute job
            try:
                self.suggestion_prefetch(exp.name)
            except Exception:
                log.debug("suggestion prefetch hook failed", exc_info=True)
        if self.metrics_registry is not None:
            bucket = {
                TrialCondition.SUCCEEDED: "succeeded",
                TrialCondition.FAILED: "failed",
                TrialCondition.KILLED: "killed",
                TrialCondition.EARLY_STOPPED: "early_stopped",
                TrialCondition.METRICS_UNAVAILABLE: "metrics_unavailable",
            }.get(trial.condition, "completed")
            self.metrics_registry.inc(f"katib_trial_{bucket}_total", experiment=exp.name)
        if self.recorder is not None:
            warning = trial.condition in (TrialCondition.FAILED, TrialCondition.METRICS_UNAVAILABLE)
            self.recorder.event(
                exp.name, "Trial", trial.name,
                trial.current_reason or trial.condition.value,
                trial.message, warning=warning,
            )
        # retainRun semantics (trial_controller.go:297 deletes the finished
        # job unless retain): clean the workdir of successfully-finished
        # trials; failed/killed/metrics-unavailable workdirs are always kept
        # for postmortem (a deviation the reference can't offer — its pods
        # are gone either way).
        from .multifidelity import PAUSED_LABEL

        if (
            not exp.spec.trial_template.retain
            and self.workdir_root
            and trial.condition in (TrialCondition.SUCCEEDED, TrialCondition.EARLY_STOPPED)
            # a rung-paused trial's workdir holds the checkpoint its
            # promotion will resume from — never clean it while paused
            and PAUSED_LABEL not in trial.labels
        ):
            import os
            import shutil

            shutil.rmtree(
                os.path.join(self.workdir_root, exp.name, trial.name),
                ignore_errors=True,
            )
