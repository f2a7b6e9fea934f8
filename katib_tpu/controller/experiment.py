"""Experiment controller — the orchestration core.

reference pkg/controller.v1beta1/experiment/experiment_controller.go. The
reconcile loop is preserved (status aggregation -> budget math -> suggestion
sync -> trial creation) but driven by trial-completion events from the
scheduler instead of K8s watches:

- budget: addCount = min(parallelTrialCount, maxTrialCount - completed)
  - active (ReconcileTrials, experiment_controller.go:274-330);
- parallel shrink deletes newest active trials first (deleteTrials :362-442);
- incomplete early-stopped trials are excluded from new suggestion requests
  (ReconcileSuggestions :449-461);
- suggestion failure fails the experiment (:470-473);
- resume/restart: budgets may be raised on a restartable completed experiment
  (IsCompletedExperimentRestartable) and the loop continues.
"""

from __future__ import annotations

import logging
import os
import queue
import threading
import time
from typing import Any, List, Optional, Sequence

from ..api.defaults import set_defaults
from ..api.spec import ExperimentSpec
from ..api.status import (
    Experiment,
    ExperimentCondition,
    ExperimentReason,
    Trial,
    TrialCondition,
)
from ..api.validation import validate_experiment
from ..db.state import ExperimentStateStore
from ..db.store import (
    BufferedObservationStore,
    ObservationStore,
    SqlObservationStore,
    SqliteObservationStore,
    observation_available,
    open_store,
)
from ..earlystop.medianstop import registered_early_stoppers
from ..suggest.base import registered_algorithms
from .scheduler import TrialScheduler
from .status import is_completed_experiment_restartable, update_experiment_status
from .suggestion import (
    SuggestionFailed,
    SuggestionService,
    suggestion_request_plan,
)

log = logging.getLogger("katib_tpu.experiment")


class ExperimentController:
    """Single-process orchestrator owning state, scheduler and suggestions.

    Replaces cmd/katib-controller (manager + 3 controllers + webhooks).
    """

    def __init__(
        self,
        root_dir: Optional[str] = None,
        devices: Optional[Sequence[Any]] = None,
        persist: bool = True,
        config: Optional["KatibConfig"] = None,
    ):
        from ..analysis.lockgraph import maybe_install_from_env
        from ..config import load_config

        # KATIB_TPU_LOCKCHECK=1: instrument lock construction BEFORE the
        # locked subsystems (scheduler, obslog, tracer, sampler) are built,
        # so the dynamic lock-order detector sees every acquisition
        # (analysis/lockgraph.py; cycle report logged at exit)
        maybe_install_from_env()
        self.config = config if config is not None else load_config()
        rt = self.config.runtime
        from ..analysis import program as semantic_analysis

        # one switch for every consumer, including the lock-free dispatch
        # paths (packing keys, fingerprint-grouped ordering)
        semantic_analysis.set_enabled(rt.semantic_analysis)
        from ..runtime import population as fused_population

        # same one-switch pattern for the fused population runtime: pack
        # capacity, executor selection and the fused reconcile branch all
        # consult runtime_enabled()
        fused_population.set_enabled(rt.fused_population)
        from ..suggest import vectorized as vectorized_suggest

        # vectorized suggestion plane (suggest/vectorized.py, ISSUE 10):
        # one switch consulted by the TPE/CMA-ES/BO hot paths;
        # vector_suggest=false / KATIB_TPU_VECTOR_SUGGEST=0 restores the
        # legacy NumPy suggesters byte-identically
        vectorized_suggest.set_enabled(rt.vector_suggest)
        if rt.xla_cache_min_compile_seconds:
            from ..utils.compilation import ENV_MIN_COMPILE_SECS

            # same propagation for the persisted-entry threshold: lazy
            # enables in this process and trial subprocesses must agree on
            # what gets persisted (ISSUE 8 satellite). Only a non-default
            # threshold needs stamping — the in-repo default (persist
            # everything) is what children fall back to anyway.
            os.environ.setdefault(
                ENV_MIN_COMPILE_SECS, str(rt.xla_cache_min_compile_seconds)
            )
        self.root_dir = root_dir
        state_root = os.path.join(root_dir, "state") if (root_dir and persist) else None
        db_path = os.path.join(root_dir, "observations.db") if root_dir else None
        self.state = ExperimentStateStore(state_root)
        from .events import EventRecorder, MetricsRegistry

        self.events = EventRecorder()
        self.metrics = MetricsRegistry()
        # Crash-tolerant controller (controller/recovery.py, ISSUE 14):
        # lease-fenced single-writer on the state root + the recovery
        # journal. The lease is acquired BEFORE any other subsystem opens
        # the root for writing (obslog, tracer, compile registry), so a
        # second controller is fenced out before it can corrupt anything.
        # Disabled (runtime.recovery=false / KATIB_TPU_RECOVERY=0, or no
        # persisted root) nothing is constructed and every consult below
        # is one `is None` check.
        self.lease = None
        self.journal = None
        if rt.recovery and state_root:
            from .recovery import ControllerLease, RecoveryJournal, journal_dir

            if rt.replicas > 0:
                # Sharded control plane (controller/placement.py, ISSUE 15):
                # per-experiment placement leases replace the root-wide
                # single-writer — N replicas share this root, each owning a
                # disjoint experiment set — and each replica journals under
                # its own subdir so cross-process appends never collide on a
                # segment name. Replay walks every subdir (merged records)
                # so a failover replica sees the dead owner's intents.
                from .placement import replica_id

                self.journal = RecoveryJournal(
                    journal_dir(root_dir, replica=replica_id())
                )
            else:
                self.lease = ControllerLease(
                    state_root,
                    ttl_seconds=rt.controller_lease_seconds,
                    standby=rt.controller_lease_standby,
                    events=self.events,
                    metrics=self.metrics,
                ).acquire()
                self.journal = RecoveryJournal(journal_dir(root_dir))
        store: ObservationStore = open_store(db_path, backend=rt.obslog_backend)
        # SqlObservationStore covers every dialect behind the ISSUE 17 seam
        # (SQLite and Postgres alike): the write-behind sits ABOVE the seam
        if rt.obslog_buffered and isinstance(store, SqlObservationStore):
            # group-commit write-behind pipeline (docs/data-plane.md): the
            # in-process hot path enqueues instead of paying a per-report
            # commit. Subprocess env bindings and the native engine keep
            # their direct-write paths; the memory store has no commit to
            # amortize.
            store = BufferedObservationStore(
                store,
                max_buffered_rows=rt.obslog_buffer_rows,
                metrics=self.metrics,
            )
        self.obs_store: ObservationStore = store
        self.db_path = db_path
        # Tenancy plane (service/tenancy.py, ISSUE 17): the registry is only
        # constructed when the knob is on, so every enforcement site reduces
        # to `registry is None` and tenancy-off stays byte-identical.
        self.tenants = None
        if rt.tenancy and root_dir:
            from ..service.tenancy import TenantRegistry

            self.tenants = TenantRegistry(root_dir)
        from ..tracing import Tracer

        self.tracer = Tracer(
            enabled=rt.tracing,
            ring_size=rt.trace_ring_spans,
            persist_dir=os.path.join(root_dir, "traces") if root_dir else None,
        )
        if rt.wire_tracing and root_dir:
            # distributed tracing plane (ISSUE 19): every ended span is also
            # appended durably under the SHARED root keyed by trace id, so a
            # cross-replica trace merges into one tree even after this
            # replica is SIGKILLed mid-trial
            from ..tracing import WireSpanSink

            from .placement import replica_id

            self.tracer.attach_wire_sink(WireSpanSink(root_dir, replica_id()))
        from ..telemetry import ResourceSampler

        self.telemetry = ResourceSampler(
            enabled=rt.telemetry,
            interval=rt.telemetry_interval_seconds,
            metrics=self.metrics,
            events=self.events,
            persist_dir=os.path.join(root_dir, "telemetry") if root_dir else None,
            stall_seconds=rt.stall_seconds,
            oom_risk_fraction=rt.oom_risk_fraction,
            ring_size=rt.telemetry_ring_samples,
        )
        self.telemetry.start()
        self.suggestions = SuggestionService(
            self.state,
            self.obs_store,
            config=self.config,
            metrics=self.metrics,
            events=self.events,
            tenants=self.tenants,
        )
        # add_collector, not set_collector: the telemetry sampler registered
        # its own gauge hook on the same registry
        self.metrics.add_collector(
            self._collect_current_gauges,
            names=("katib_experiments_current", "katib_trials_current"),
        )
        # Native multi-fidelity engine (controller/multifidelity.py, ISSUE
        # 11): ASHA rung ladders owned by the scheduler — pause at rung
        # boundaries, checkpoint-resumed promotions, reconcile-side pruning.
        # Disabled (runtime.multifidelity=false / KATIB_TPU_MULTIFIDELITY=0)
        # nothing is constructed, `algorithm: asha` specs are rejected at
        # admission, and the legacy hyperband path is byte-identical.
        self.multifidelity = None
        if rt.multifidelity:
            from .multifidelity import MultiFidelityEngine

            self.multifidelity = MultiFidelityEngine(
                self.state,
                self.obs_store,
                events=self.events,
                metrics=self.metrics,
                # dwell-window promotion packing (ISSUE 13): same-rung
                # promotions batch under one dispatch barrier so rung 1+
                # dispatches as vmapped packs; 0 = submit at the decision
                # point, byte-identical to PR 11
                dwell_seconds=rt.promotion_dwell_seconds,
                journal=self.journal,
            )
        self._completed_seen: set = set()
        self._closed = threading.Event()
        # AOT compile service (compilesvc/service.py, ISSUE 8): compilation
        # as a scheduled resource — admission-time AOT compiles on a worker
        # pool, fingerprint-keyed executable registry, compile-gated
        # dispatch. Disabled (runtime.compile_service=false /
        # KATIB_TPU_COMPILE_SERVICE=0) nothing is constructed and the
        # scheduler's legacy dispatch is byte-identical.
        self.compile_service = None
        if rt.compile_service:
            from ..compilesvc.service import CompileService

            self.compile_service = CompileService(
                workers=rt.compile_workers,
                timeout_seconds=rt.compile_timeout_seconds,
                metrics=self.metrics,
                events=self.events,
                tracer=self.tracer,
                persist_dir=(
                    os.path.join(root_dir, "compilesvc") if root_dir else None
                ),
            )
            self.compile_service.start()
        # Supervised device plane (controller/deviceplane.py, ISSUE 12):
        # device sets as leased, revocable resources with zombie-lease
        # reclaim, device-loss-as-preemption, backend failover and chaos
        # hooks. Disabled (runtime.device_plane=false /
        # KATIB_TPU_DEVICE_PLANE=0) nothing is constructed and the
        # scheduler's legacy free-list allocator is byte-identical.
        self.device_plane = None
        if rt.device_plane:
            from .deviceplane import DevicePlane

            self.device_plane = DevicePlane(
                events=self.events,
                metrics=self.metrics,
                probe_timeout_seconds=rt.device_probe_timeout_seconds,
                reprobe_interval_seconds=rt.device_reprobe_interval_seconds,
                zombie_lease_seconds=rt.device_lease_seconds,
                heartbeat_timeout_seconds=rt.device_heartbeat_timeout_seconds,
                failover=rt.device_failover,
                persist_dir=(
                    os.path.join(root_dir, "deviceplane") if root_dir else None
                ),
            )
            self.device_plane.start()
        # Step-statistics plane (controller/stepstats.py + runtime/
        # stepstats.py, ISSUE 20): per-step timing/throughput/MFU series
        # under the reserved katib-tpu/perf/ namespace, per-experiment
        # rollups on /metrics, and the RetraceStorm / GangStraggler /
        # StepTimeRegression detectors. Disabled (default,
        # runtime.step_stats=false / KATIB_TPU_STEP_STATS unset) nothing is
        # constructed: wire, span set, /metrics, and observation rows are
        # byte-identical.
        self.step_stats = None
        if rt.step_stats:
            from .stepstats import StepStatsPlane

            self.step_stats = StepStatsPlane(
                metrics=self.metrics,
                events=self.events,
                flush_steps=rt.step_stats_flush_steps,
                retrace_storm_threshold=rt.retrace_storm_threshold,
                straggler_ratio=rt.straggler_ratio,
                regression_ratio=rt.step_regression_ratio,
            )
        workdir_root = os.path.join(root_dir, "trials") if root_dir else None
        self.scheduler = TrialScheduler(
            self.state,
            self.obs_store,
            devices=devices,
            db_path=db_path,
            workdir_root=workdir_root,
            events=self.events,
            metrics=self.metrics,
            trial_timeout=rt.trial_timeout_seconds,
            max_trial_restarts=rt.max_trial_restarts,
            poll_interval=rt.metrics_poll_interval,
            devices_per_host=rt.devices_per_host,
            queue_stall_seconds=rt.queue_stall_seconds,
            aging_seconds=rt.fairshare_aging_seconds,
            preemption_grace_seconds=rt.preemption_grace_seconds,
            tracer=self.tracer,
            telemetry=self.telemetry,
            compile_service=self.compile_service,
            compile_gate_seconds=rt.compile_gate_seconds,
            fused_population=rt.fused_population,
            population_chunk_generations=rt.population_chunk_generations,
            population_stream=rt.population_stream_telemetry,
            # async suggestion pipeline (ISSUE 10): a terminal trial means
            # the next batch's history just changed — the hook starts the
            # precompute before the reconcile loop consults
            suggestion_prefetch=(
                self.suggestions.notify_trials_changed
                if rt.async_suggest
                else None
            ),
            multifidelity=self.multifidelity,
            device_plane=self.device_plane,
            journal=self.journal,
            step_stats=self.step_stats,
        )

    # -- lifecycle -----------------------------------------------------------

    def create_experiment(self, spec: ExperimentSpec) -> Experiment:
        """Defaulting + validation webhooks, then experiment creation
        (SURVEY.md §3.1)."""
        set_defaults(spec, default_parallel=self.config.runtime.default_parallel_trial_count)
        validate_experiment(
            spec,
            known_algorithms=registered_algorithms(),
            known_early_stopping=registered_early_stoppers(),
        )
        from .multifidelity import ENGINE_ALGORITHMS

        if spec.algorithm.algorithm_name in ENGINE_ALGORITHMS and self.multifidelity is None:
            from ..api.validation import ValidationError

            raise ValidationError(
                [
                    f"algorithm {spec.algorithm.algorithm_name!r} requires "
                    "the multi-fidelity engine: set runtime.multifidelity=true "
                    "(KATIB_TPU_MULTIFIDELITY=1)"
                ]
            )
        # semantic pre-flight (ISSUE 7): rejects a certainly-OOM sweep at
        # admission (raises ValidationError) and warms the analysis cache
        # for the dispatch-path consumers; near-capacity warning deferred
        # until the experiment exists to attach the event to
        hbm_warning = self._semantic_preflight(spec)
        exp = Experiment(spec=spec)
        exp.status.set_condition(
            ExperimentCondition.CREATED, ExperimentReason.NONE, "Experiment is created"
        )
        self.suggestions.forget(spec.name)  # stale state from a deleted namesake
        self.state.create_experiment(exp)
        self.metrics.inc("katib_experiment_created_total", experiment=spec.name)
        self.events.event(spec.name, "Experiment", spec.name, "ExperimentCreated", "Experiment is created")
        # Algorithm/early-stopping settings dry-run (validator.go:203-238 +
        # suggestion_controller.go:256-271). Done at admission like the
        # reference's validating webhook.
        self.suggestions.validate(exp)
        if hbm_warning:
            self.events.event(
                spec.name, "Experiment", spec.name,
                "PredictedHbmNearCapacity", hbm_warning, warning=True,
            )
        if self.compile_service is not None:
            # admission-time prewarm: the spec's baseline dispatch group
            # starts compiling before the first suggestion batch, so a
            # runtime-scalar sweep's shared executable is warm (or at least
            # compiling) by the time trials queue
            try:
                self.compile_service.prewarm(spec)
            except Exception:
                log.debug("compile prewarm failed", exc_info=True)
            # fused population sweeps: the whole G-generation scan program
            # is fingerprinted and AOT-prewarmed like any dispatch group,
            # so the sweep compiles exactly once — in the service, before
            # chips are allocated (best-effort inside prewarm_fused)
            from ..runtime import population as fused_population

            fused_population.prewarm_fused(
                self.compile_service, spec,
                self.config.runtime.population_chunk_generations,
            )
        return exp

    def _semantic_preflight(self, spec: ExperimentSpec) -> Optional[str]:
        """Jaxpr-level admission pre-flight (analysis/program.py),
        complementing the PR 5 runtime OOM watchdog: trace the trial's
        abstract program under the search space's baseline avals and
        reject (ValidationError) when the predicted peak HBM — a lower
        bound — already exceeds device memory. Returns a near-capacity
        warning string, or None. Best-effort by design: probes are opt-in
        and analysis failures admit the experiment unchanged."""
        rt = self.config.runtime
        if not rt.semantic_analysis:
            return None
        from ..analysis import program as semantic
        from ..api.validation import (
            ValidationError,
            predicted_memory_errors,
            predicted_memory_warning,
        )

        analysis = semantic.cached_analysis(spec)
        if analysis is None or not analysis.analyzable or analysis.cost is None:
            return None
        capacity = rt.device_hbm_bytes or semantic.device_capacity_bytes()
        if not capacity:
            return None
        errs = predicted_memory_errors(
            analysis.cost.peak_bytes, capacity, analysis.target
        )
        if errs:
            raise ValidationError(errs)
        return predicted_memory_warning(
            analysis.cost.peak_bytes, capacity, analysis.target
        )

    def edit_experiment_budget(
        self,
        name: str,
        max_trial_count: Optional[int] = None,
        parallel_trial_count: Optional[int] = None,
        max_failed_trial_count: Optional[int] = None,
    ) -> Experiment:
        """Budget edit / restart — the only legal spec mutation
        (validator.go:139-144; SDK edit_experiment_budget)."""
        exp = self.state.get_experiment(name)
        if exp is None:
            raise KeyError(f"experiment {name!r} not found")
        new_spec = ExperimentSpec.from_json(exp.spec.to_json())
        new_spec.trial_template.function = exp.spec.trial_template.function
        if max_trial_count is not None:
            new_spec.max_trial_count = max_trial_count
        if parallel_trial_count is not None:
            new_spec.parallel_trial_count = parallel_trial_count
        if max_failed_trial_count is not None:
            new_spec.max_failed_trial_count = max_failed_trial_count
        validate_experiment(new_spec, old=exp, known_algorithms=registered_algorithms())
        exp.spec = new_spec
        if exp.status.is_completed and is_completed_experiment_restartable(exp):
            # Restarting condition (experiment_controller.go:187-206)
            exp.status.set_condition(
                ExperimentCondition.RESTARTING, ExperimentReason.NONE, "Experiment is restarted"
            )
            exp.status.completion_time = None
            self._completed_seen.discard(name)
        self.state.update_experiment(exp)
        return exp

    # -- reconcile -----------------------------------------------------------

    def reconcile(self, name: str) -> Experiment:
        """One reconcile pass (experiment_controller.go:156-247)."""
        exp = self.state.get_experiment(name)
        if exp is None:
            raise KeyError(f"experiment {name!r} not found")
        trials = self.state.list_trials(name)
        mf = self.multifidelity
        if mf is not None and not exp.status.is_completed and mf.applies(exp.spec):
            # rung decisions ride the reconcile wake: promote newly-eligible
            # paused trials (making them active again) BEFORE the status
            # aggregation below can declare the experiment complete, and
            # prune the ladder's leftovers once the sweep drains
            try:
                if mf.pump(exp, trials, self.scheduler):
                    trials = self.state.list_trials(name)
            except Exception:
                log.warning("multifidelity pump failed", exc_info=True)
        update_experiment_status(exp, trials, self.suggestions.search_ended(name))
        if not exp.status.is_completed:
            try:
                self._reconcile_trials(exp, trials)
            except SuggestionFailed as e:
                exp.status.set_condition(
                    ExperimentCondition.FAILED,
                    ExperimentReason.SUGGESTION_FAILED,
                    str(e),
                )
        if exp.status.is_completed and name not in self._completed_seen:
            self._completed_seen.add(name)
            self._on_completed(exp)
        self.state.update_experiment(exp)
        return exp

    def _collect_current_gauges(self) -> dict:
        """katib_experiments_current / katib_trials_current by last condition,
        recomputed from LIVE state at every /metrics scrape (registered as
        the MetricsRegistry collector — the reference's custom-collector
        pattern, trial/util/prometheus_metrics.go collect). Scrape-time
        recompute means no mutation path can leave them stale: late status
        flips, post-run straggler kills, and deleted experiments all read
        correctly on the next scrape. Returns the full gauge map; the
        registry swaps it in atomically."""
        key = self.metrics.gauge_key
        gauges: dict = {}
        for exp in self.state.list_experiments():
            for cond in ExperimentCondition:
                gauges[
                    key("katib_experiments_current", experiment=exp.name, status=cond.value)
                ] = 1.0 if cond == exp.status.condition else 0.0
            counts: dict = {}
            for t in self.state.list_trials(exp.name):
                counts[t.condition.value] = counts.get(t.condition.value, 0) + 1
            for cond in TrialCondition:
                gauges[
                    key("katib_trials_current", experiment=exp.name, status=cond.value)
                ] = float(counts.get(cond.value, 0))
        return gauges

    def _reconcile_trials(self, exp: Experiment, trials: List[Trial]) -> None:
        from ..runtime import population as fused_population

        if fused_population.fused_applicable(exp.spec) is None:
            # opted-in population sweep: no per-generation suggestion sync —
            # the whole sweep dispatches once as one fused gang unit
            self._reconcile_fused(exp, trials)
            return
        sts = exp.status
        parallel = exp.spec.parallel_trial_count or 1
        active = sts.trials_pending + sts.trials_running

        if active > parallel:
            mf = self.multifidelity
            if mf is not None and mf.applies(exp.spec):
                # rung promotions resubmit paused trials outside the budget
                # math, so a multi-fidelity experiment can transiently hold
                # more active trials than parallelTrialCount. Killing the
                # newest would burn an admitted-but-never-evaluated config;
                # instead admission simply waits (the device allocator still
                # bounds real concurrency) until promotions drain.
                return
            self._delete_trials(exp, trials, active - parallel)
            return
        if active >= parallel:
            return
        # Budget math + incomplete-early-stopped exclusion
        # (experiment_controller.go:274-330, :449-461) — shared with the
        # async prefetch worker so both compute identical request numbers.
        add_count, requests = suggestion_request_plan(
            exp, trials, lambda t: self._observation_available(exp, t)
        )
        if add_count <= 0:
            return

        suggest_start = time.time()
        assignments = self.suggestions.sync_assignments(exp, trials, requests)
        suggest_end = time.time()
        if self.journal is not None and assignments:
            # journal the committed batch BEFORE any trial record exists: a
            # crash inside the loop below leaves assignments whose trials
            # were never persisted, and replay (load_experiment) completes
            # them from the persisted SuggestionState instead of leaving
            # them orphaned until the next reconcile recomputes the plan
            self.journal.append(
                "suggest", exp.name,
                trials=[a.name for a in assignments[:add_count]],
            )
        # Deferred dispatch under the scheduler's barrier: queue the whole
        # batch first, then one dispatch pass — pack formation
        # (controller/packing.py) needs the batch's packable trials waiting
        # TOGETHER, or the first would start solo on free devices before
        # its pack-mates are submitted. The barrier also blocks CONCURRENT
        # dispatch triggers (a compile finishing in the service, another
        # trial releasing its gang) from splitting the batch mid-submit.
        with self.scheduler.dispatch_barrier():
            for assignment in assignments[:add_count]:
                trial = Trial.from_assignment(assignment, exp.name)
                trial.labels["katib-tpu/experiment"] = exp.name
                if self.journal is not None:
                    # write-ahead: the submit intent is durable before the
                    # trial record, so the exactly-once commit has a crash
                    # edge, not just the thread-race edge under the barrier
                    self.journal.append("submit", exp.name, trial=trial.name)
                self.state.create_trial(trial)
                if self.tracer.enabled:
                    # the trial's trace starts where its lifecycle did: at
                    # the suggestion batch that produced it. Every trial of
                    # the batch carries the same `suggestion` child span
                    # window.
                    root = self.tracer.begin_trial(
                        exp.name, trial.name, start=suggest_start
                    )
                    if root is not None:
                        self.tracer.record_span(
                            "suggestion", exp.name, root.trace_id, root.span_id,
                            start=suggest_start, end=suggest_end,
                            algorithm=exp.spec.algorithm.algorithm_name,
                            batch=len(assignments),
                        )
                checkpoint_dir = self._checkpoint_dir_for(exp, trial)
                self.scheduler.submit(
                    exp, trial, checkpoint_dir=checkpoint_dir, dispatch=False
                )

    def _reconcile_fused(self, exp: Experiment, trials: List[Trial]) -> None:
        """Dispatch (or supervise) one fused population sweep
        (runtime/population.py): K member trials — one per population slot,
        alive for the whole sweep — are created once, submitted as a batch
        and pack-formed into ONE gang unit that the scheduler routes to the
        FusedPopulationExecutor. The suggestion plane never runs; search
        end is declared at submission, so the experiment completes exactly
        when the sweep's members reach their terminal conditions."""
        from ..api.spec import ParameterAssignment
        from ..runtime import population as pop

        if trials:
            if all(t.is_terminal for t in trials):
                # re-assert after a controller restart (the fresh
                # SuggestionService lost the in-memory search-end mark)
                self.suggestions.mark_search_ended(exp.name)
            return
        try:
            program = pop.build_program(exp.spec)
            members = (
                program.initial_assignments(program.seed)
                if program.initial_assignments is not None
                else [{} for _ in range(program.n_population)]
            )
            total = pop.generation_count(exp.spec, program)
        except Exception as e:
            raise SuggestionFailed(
                f"fused population program construction failed: "
                f"{type(e).__name__}: {e}"
            )
        self.events.event(
            exp.name, "Experiment", exp.name, "PopulationFused",
            f"dispatching {program.n_population} members x {total} "
            "generations as one fused compiled program "
            f"({pop.SETTING_GENERATIONS}={total})",
        )
        ck_root = (
            os.path.join(self.root_dir, "fusedpop", exp.name)
            if self.root_dir
            else None
        )
        suggest_ts = time.time()
        # The barrier makes the K-member submission atomic: a concurrent
        # dispatch (e.g. the admission-prewarmed fused program turning warm
        # in the compile service mid-submit) must never see a partial
        # population — a split fused pack would run each fragment as its
        # own full sweep.
        with self.scheduler.dispatch_barrier():
            for i, params in enumerate(members):
                trial = Trial(
                    name=pop.member_name(exp.spec, i),
                    experiment_name=exp.name,
                    parameter_assignments=[
                        ParameterAssignment(k, v) for k, v in sorted(params.items())
                    ],
                    labels={
                        pop.FUSED_LABEL: str(i),
                        "katib-tpu/experiment": exp.name,
                    },
                )
                self.state.create_trial(trial)
                if self.tracer.enabled:
                    root = self.tracer.begin_trial(
                        exp.name, trial.name, start=suggest_ts
                    )
                    if root is not None:
                        self.tracer.record_span(
                            "suggestion", exp.name, root.trace_id, root.span_id,
                            start=suggest_ts, end=suggest_ts,
                            algorithm=exp.spec.algorithm.algorithm_name,
                            fused=True, batch=len(members),
                        )
                self.scheduler.submit(
                    exp, trial, checkpoint_dir=ck_root, dispatch=False
                )
            # the sweep IS the search: once its members finish, no further
            # suggestions exist, and active==0 + search-end completes the
            # experiment
            self.suggestions.mark_search_ended(exp.name)

    @staticmethod
    def _observation_available(exp: Experiment, trial: Trial) -> bool:
        return observation_available(trial.observation, exp.spec.objective)

    def _checkpoint_dir_for(self, exp: Experiment, trial: Trial) -> Optional[str]:
        """PBT trials get their lineage directory (the suggestion-PVC mount,
        inject_webhook.go:334+)."""
        suggester = self.suggestions._suggesters.get(exp.name)
        if suggester is not None and hasattr(suggester, "checkpoint_dir"):
            try:
                return suggester.checkpoint_dir(trial.name)
            except Exception:
                return None
        return None

    def _delete_trials(self, exp: Experiment, trials: List[Trial], count: int) -> None:
        """Parallel-shrink: kill newest active trials (deleteTrials :362-442)."""
        active = [
            t
            for t in trials
            if t.condition in (TrialCondition.PENDING, TrialCondition.RUNNING, TrialCondition.CREATED)
        ]
        active.sort(key=lambda t: t.start_time or float("inf"), reverse=True)
        suggestion = self.state.get_suggestion(exp.name)
        doomed = active[:count]
        for t in doomed:
            self.scheduler.kill(t.name)
        if suggestion is not None:
            names = {t.name for t in doomed}
            suggestion.suggestions = [a for a in suggestion.suggestions if a.name not in names]
            suggestion.requests = len(suggestion.suggestions)
            self.state.put_suggestion(suggestion)

    def _on_completed(self, exp: Experiment) -> None:
        if self.multifidelity is not None:
            # goal-reached / budget-exhausted completion can leave trials
            # rung-paused; prune them so none lingers awaiting a promotion
            # that will never come
            self.multifidelity.finalize(exp)
        # transfer-HPO index (ISSUE 10): completed observations become
        # warm-start priors for future experiments with a matching
        # search-space + objective signature
        self.suggestions.index_completed_history(exp)
        self.suggestions.cleanup(exp)
        outcome = "succeeded" if exp.status.is_succeeded else "failed"
        self.metrics.inc(f"katib_experiment_{outcome}_total", experiment=exp.name)
        self.events.event(
            exp.name, "Experiment", exp.name,
            exp.status.reason.value or exp.status.condition.value,
            exp.status.message,
            warning=not exp.status.is_succeeded,
        )

    # -- run loop ------------------------------------------------------------

    def run(self, name: str, timeout: Optional[float] = None, poll_interval: float = 0.5) -> Experiment:
        """Drive the experiment to completion (replaces the controller-runtime
        event loop; wakes on scheduler events instead of requeues)."""
        deadline = None if timeout is None else time.time() + timeout
        exp = self.reconcile(name)
        while not exp.status.is_completed:
            if self._closed.is_set():
                # controller shut down (close()) — stop driving so no run
                # thread keeps submitting trials / holding chips past intent
                break
            if deadline is not None and time.time() > deadline:
                raise TimeoutError(f"experiment {name!r} did not complete in {timeout}s")
            try:
                self.scheduler.events.get(timeout=poll_interval)
            except queue.Empty:
                pass
            exp = self.reconcile(name)
        # drain this experiment's still-running trials (goal-reached leaves
        # stragglers); other experiments sharing the controller are untouched.
        # NOT on shutdown: close() already killed them with the
        # SchedulerShutdown reason — a kill() here would record them as
        # deliberate and defeat requeue-on-resume.
        if not self._closed.is_set():
            for t in self.state.list_trials(name):
                if not t.is_terminal:
                    self.scheduler.kill(t.name)
            # settle the allocator before handing control back: a trial's
            # terminal status is persisted a beat before its worker thread
            # releases the gang allocation, so without this a caller that
            # immediately reuses the chips (or asserts free_count) races the
            # last release. Bounded: a zombie trial in its kill-grace window
            # stops the wait at the deadline rather than hanging the caller.
            if not self.scheduler.quiesce(name, timeout=10.0):
                # hitting the deadline means a zombie gang still holds chips
                # — make it visible instead of returning silently
                self.events.event(
                    name, "Experiment", name, "QuiesceTimeout",
                    "scheduler did not quiesce within 10s after completion; "
                    "a zombie trial may still hold its gang allocation "
                    "(see /api/queue devices.quarantined)",
                    warning=True,
                )
        return exp

    def load_experiment(self, name: str) -> Experiment:
        """Cross-process resume — the FromVolume PVC semantics
        (composer.go:296+, suggestion_controller.go:132-143): restore the
        experiment, its trials, and the suggestion state (incl. the
        algorithm-settings round-trip hyperband depends on) from the state
        dir, then requeue trials that were in flight when the previous
        controller process died. Stateful suggesters resume from their own
        on-disk state (ENAS controller pickle, PBT queue snapshot) when the
        fresh instance is created lazily on the next suggestion sync.

        Trials of in-memory ``function`` templates cannot be re-executed in a
        new process (the callable does not serialize — the reference's
        equivalent constraint is that runSpecs are declarative YAML); such
        in-flight trials are marked Killed instead of requeued.

        With recovery enabled (``runtime.recovery``, the default) the
        restart is CHECKPOINT-PRESERVING: the journal is replayed first
        (crash-edge intents — a journaled terminal transition or a
        committed-but-unpersisted suggestion — are completed), orphaned
        trial processes of the previous incarnation are fenced, and each
        in-flight trial's observation log is truncated only to its last
        durable checkpoint instead of dropped, the whole batch requeued
        under one dispatch barrier so packed/fused gangs re-form. With
        ``KATIB_TPU_RECOVERY=0`` the legacy path below runs byte-identically.
        """
        exp = self.state.load(name)
        if exp is None:
            raise KeyError(f"no persisted state for experiment {name!r}")
        self._completed_seen.discard(name)
        if exp.status.is_completed:
            self._completed_seen.add(name)
            return exp
        if self.config.runtime.recovery and self.journal is not None:
            return self._load_with_recovery(exp)
        resumable = exp.spec.trial_template.function is None
        for trial in self.state.list_trials(name):
            # look up the Killed condition entry by TYPE — _update_conditions
            # replaces same-type entries in place, so conditions[-1] can be a
            # stale earlier state after a kill/requeue/fail history
            killed_cond = next(
                (
                    c
                    for c in trial.conditions
                    if c.type == TrialCondition.KILLED.value
                ),
                None,
            )
            shutdown_killed = (
                trial.condition == TrialCondition.KILLED
                and killed_cond is not None
                and killed_cond.reason == "SchedulerShutdown"
            )
            if trial.is_terminal and not shutdown_killed:
                continue
            if self.scheduler.is_active(trial.name):
                continue  # idempotence: a second load must not double-submit
            if resumable:
                checkpoint_dir = None
                try:
                    self.suggestions.suggester_for(exp)
                    checkpoint_dir = self._checkpoint_dir_for(exp, trial)
                except Exception:
                    pass  # suggester re-creation fails loudly on next sync
                # the re-run starts clean: drop the interrupted run's metrics
                # so the observation fold can't mix two executions
                self.obs_store.delete_observation_log(trial.name)
                self.events.event(
                    exp.name, "Trial", trial.name, "TrialResubmitted",
                    "controller restarted; in-flight trial re-queued",
                )
                self.scheduler.submit(exp, trial, checkpoint_dir=checkpoint_dir)
            else:
                trial.set_condition(
                    TrialCondition.KILLED,
                    "TrialLost",
                    "in-memory trial function lost on controller restart",
                )
                self.state.update_trial(trial)
        return exp

    # -- crash recovery (controller/recovery.py, ISSUE 14) -------------------

    def _load_with_recovery(self, exp: Experiment) -> Experiment:
        """Checkpoint-preserving restart: journal replay, orphan fencing,
        truncate-to-checkpoint, and a single-barrier requeue."""
        from ..runtime import population as fused_population
        from . import recovery

        t0 = time.time()
        name = exp.name
        journal_high, consumed_files = self._replay_journal(exp)
        resumable = exp.spec.trial_template.function is None
        requeue: List[Trial] = []
        for trial in self.state.list_trials(name):
            killed_cond = next(
                (
                    c
                    for c in trial.conditions
                    if c.type == TrialCondition.KILLED.value
                ),
                None,
            )
            shutdown_killed = (
                trial.condition == TrialCondition.KILLED
                and killed_cond is not None
                and killed_cond.reason == "SchedulerShutdown"
            )
            if trial.is_terminal and not shutdown_killed:
                # terminal trials — including rung-paused (EarlyStopped +
                # PAUSED_LABEL) ones — keep their rows; the multi-fidelity
                # engine rejoins them on the first pump via the persisted
                # label rebuild (multifidelity._entry)
                continue
            if self.scheduler.is_active(trial.name):
                continue  # idempotence: a second load must not double-submit
            if not resumable:
                trial.set_condition(
                    TrialCondition.KILLED,
                    "TrialLost",
                    "in-memory trial function lost on controller restart",
                )
                self.state.update_trial(trial)
                continue
            requeue.append(trial)
        fenced = resubmitted = resumed_from_ckpt = 0
        rows_preserved = rows_truncated = 0
        fused_ck_time: Optional[float] = None
        # ONE barrier around the whole batch: pack formation must see every
        # in-flight member together, so fused sweeps and packed gangs
        # re-form from their carry checkpoints instead of the first member
        # dispatching solo (exactly the batch-submit invariant of
        # _reconcile_trials, now applied to the restart path)
        with self.scheduler.dispatch_barrier():
            for trial in requeue:
                workdir = (
                    os.path.join(self.root_dir, "trials", name, trial.name)
                    if self.root_dir
                    else None
                )
                if recovery.fence_stale_trial_process(workdir, trial.name):
                    fenced += 1
                checkpoint_dir = None
                if fused_population.FUSED_LABEL in trial.labels and self.root_dir:
                    # fused sweep members share the chunk-boundary carry
                    # checkpoint — the same dir _reconcile_fused dispatched
                    # them with (it wins over any suggester lineage dir), so
                    # the re-formed gang resumes mid-sweep
                    checkpoint_dir = os.path.join(self.root_dir, "fusedpop", name)
                else:
                    try:
                        self.suggestions.suggester_for(exp)
                        checkpoint_dir = self._checkpoint_dir_for(exp, trial)
                    except Exception:
                        pass  # suggester re-creation fails loudly on next sync
                ck_time = recovery.latest_checkpoint_time(
                    checkpoint_dir or workdir
                )
                if (
                    ck_time is not None
                    and fused_population.FUSED_LABEL in trial.labels
                ):
                    fused_ck_time = ck_time
                if ck_time is None:
                    # no durable checkpoint: the re-run starts clean — the
                    # legacy invariant, unchanged
                    self.obs_store.delete_observation_log(trial.name)
                    detail = "re-running from scratch"
                else:
                    rows_truncated += self.obs_store.truncate_observation_log(
                        trial.name, ck_time
                    )
                    kept = len(self.obs_store.get_observation_log(trial.name))
                    rows_preserved += kept
                    resumed_from_ckpt += 1
                    detail = (
                        f"resuming from checkpoint ({kept} observation "
                        "row(s) preserved)"
                    )
                self.events.event(
                    name, "Trial", trial.name, "TrialResubmitted",
                    f"controller restarted; in-flight trial re-queued, {detail}",
                )
                self.scheduler.submit(
                    exp, trial, checkpoint_dir=checkpoint_dir, dispatch=False
                )
                resubmitted += 1
            if fused_ck_time is not None:
                # the fused demux writes population best/median rows under
                # the <exp>-population pseudo-trial AFTER the carry save;
                # the resumed sweep re-demuxes everything past the carry, so
                # the pseudo log's tail must be truncated with the members'
                rows_truncated += self.obs_store.truncate_observation_log(
                    f"{name}-population", fused_ck_time
                )
        if consumed_files is not None:
            # sharded mode: the replayed records may live in ANOTHER
            # replica's journal subdir — remove exactly the consumed
            # segments instead of compacting by our own seq counter
            recovery.remove_journal_files(consumed_files)
        elif journal_high:
            # intents at or below the replayed high-water mark are consumed;
            # the requeued batch writes fresh ones
            self.journal.compact(name, journal_high)
        replay_seconds = time.time() - t0
        self.metrics.inc("katib_recovery_replays_total", experiment=name)
        self.metrics.inc(
            "katib_recovery_trials_resubmitted_total",
            value=float(resubmitted), experiment=name,
        )
        self.metrics.inc(
            "katib_recovery_rows_preserved_total",
            value=float(rows_preserved), experiment=name,
        )
        self.metrics.inc(
            "katib_recovery_rows_truncated_total",
            value=float(rows_truncated), experiment=name,
        )
        self.metrics.set_gauge(
            "katib_recovery_replay_seconds", round(replay_seconds, 6),
            experiment=name,
        )
        self.events.event(
            name, "Experiment", name, "ControllerRecovered",
            f"recovered in {replay_seconds:.3f}s: {resubmitted} in-flight "
            f"trial(s) requeued ({resumed_from_ckpt} resuming from "
            f"checkpoints, {rows_preserved} observation row(s) preserved, "
            f"{rows_truncated} un-checkpointed row(s) truncated, "
            f"{fenced} orphaned process(es) fenced)",
        )
        return exp

    def _replay_journal(self, exp: Experiment):
        """Replay this experiment's journal intents against the loaded
        state; returns ``(highest seq seen, consumed segment paths)`` —
        0 for an empty journal, and paths only in sharded mode (where the
        merged cross-replica walk knows each record's file and compaction
        removes exactly what was consumed).

        Two crash edges are closed here:

        - ``terminal`` write-ahead: the journal records a trial's terminal
          transition BEFORE the state store does, so a crash between the
          two leaves a journaled condition for a trial the state still
          calls running — apply it (refolding the observation from the
          durable rows) instead of re-running a finished trial.
        - ``suggest``/``submit`` intents naming trials that were never
          persisted: the suggestion commit is durable (SuggestionState) but
          the trial record is not — complete the commit from the persisted
          assignment so the budget math sees it immediately rather than an
          orphan the next reconcile has to re-derive.
        """
        sharded = self.config.runtime.replicas > 0
        if sharded:
            from . import recovery

            # a failover replica replays the DEAD owner's intents: walk every
            # journal subdir, ordered by (ts, seq)
            records = recovery.merged_journal_records(self.root_dir, exp.name)
        else:
            records = self.journal.records(exp.name)
        if not records:
            return 0, ([] if sharded else None)
        trials = {t.name: t for t in self.state.list_trials(exp.name)}
        suggestion = self.state.get_suggestion(exp.name)
        assignments = {
            a.name: a for a in (suggestion.suggestions if suggestion else [])
        }
        for rec in records:
            op = rec.get("op")
            if op == "terminal":
                trial = trials.get(rec.get("trial", ""))
                cond_raw = rec.get("condition")
                if trial is None or trial.is_terminal or not cond_raw:
                    continue
                try:
                    cond = TrialCondition(cond_raw)
                except ValueError:
                    continue
                trial.observation = self.obs_store.folded(
                    trial.name, exp.spec.objective.all_metric_names()
                )
                trial.set_condition(
                    cond,
                    rec.get("reason") or cond.value,
                    "terminal transition replayed from the recovery journal "
                    "(crashed between journal append and state write)",
                )
                self.state.update_trial(trial)
            elif op in ("suggest", "submit"):
                names = rec.get("trials") or (
                    [rec["trial"]] if rec.get("trial") else []
                )
                for tn in names:
                    if tn in trials or tn not in assignments:
                        continue
                    trial = Trial.from_assignment(assignments[tn], exp.name)
                    trial.labels["katib-tpu/experiment"] = exp.name
                    self.state.create_trial(trial)
                    trials[tn] = trial
        if sharded:
            return 0, [r["_file"] for r in records if r.get("_file")]
        return int(records[-1].get("seq", 0)), None

    def delete_experiment(self, name: str) -> None:
        """Delete an experiment and all its state (kubectl delete experiment)."""
        for t in self.state.list_trials(name):
            if not t.is_terminal:
                self.scheduler.kill(t.name)
            self.obs_store.delete_observation_log(t.name)
        self.obs_store.delete_experiment_history(name)
        self.suggestions.forget(name)
        self.scheduler.forget_experiment(name)
        if self.multifidelity is not None:
            self.multifidelity.forget(name)
        if self.step_stats is not None:
            self.step_stats.forget_experiment(name)
        self.tracer.forget(name)
        self._completed_seen.discard(name)
        self.metrics.inc("katib_experiment_deleted_total", experiment=name)
        self.state.delete_experiment(name)

    def close(self) -> None:
        self._closed.set()  # unhooks run() loops (incl. UI run-threads)
        self.suggestions.close()
        self.scheduler.kill_all()
        self.scheduler.join(timeout=10)
        if self.compile_service is not None:
            self.compile_service.stop()
        if self.device_plane is not None:
            self.device_plane.stop()
        self.telemetry.stop()
        self.obs_store.close()
        if self.lease is not None:
            # released LAST: every subsystem above has stopped writing the
            # root, so a standby successor taking over sees quiesced state
            self.lease.release()
