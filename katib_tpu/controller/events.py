"""Event recording + Prometheus-style controller metrics.

reference observability surface (SURVEY.md §5):
- K8s Events on every state change (r.recorder.Eventf —
  trial_controller_util.go:66/86/109);
- Prometheus CounterVec/GaugeVec for experiments/trials
  created/succeeded/failed/deleted (experiment/util/prometheus_metrics.go:29-78,
  trial/util/prometheus_metrics.go).

Here: an in-memory (optionally persisted) ring of typed events per
experiment, and a metrics registry rendered in Prometheus text exposition
format (served by katib_tpu.ui.server at /metrics).
"""

from __future__ import annotations

import collections
import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Tuple


@dataclass
class Event:
    timestamp: float
    kind: str          # Experiment | Trial
    name: str
    event_type: str    # Normal | Warning
    reason: str
    message: str
    experiment: str = ""  # owning experiment — the cross-experiment view key

    def to_dict(self):
        return {
            "timestamp": self.timestamp,
            "kind": self.kind,
            "name": self.name,
            "type": self.event_type,
            "reason": self.reason,
            "message": self.message,
            "experiment": self.experiment,
        }


class EventRecorder:
    def __init__(self, max_events: int = 1000):
        self._lock = threading.Lock()
        self._events: Dict[str, Deque[Event]] = {}
        self.max_events = max_events

    def event(
        self,
        experiment: str,
        kind: str,
        name: str,
        reason: str,
        message: str,
        warning: bool = False,
    ) -> None:
        e = Event(
            timestamp=time.time(),
            kind=kind,
            name=name,
            event_type="Warning" if warning else "Normal",
            reason=reason,
            message=message,
            experiment=experiment,
        )
        with self._lock:
            q = self._events.setdefault(experiment, collections.deque(maxlen=self.max_events))
            q.append(e)

    def list(self, experiment: str) -> List[Event]:
        with self._lock:
            return list(self._events.get(experiment, ()))

    def list_all(
        self, limit: Optional[int] = None, warning_only: bool = False
    ) -> List[Event]:
        """Cross-experiment event view, oldest first: queue stalls,
        preemptions and flusher errors are queryable without knowing which
        experiment raised them (GET /api/events?warning=1)."""
        with self._lock:
            merged = [e for q in self._events.values() for e in q]
        merged.sort(key=lambda e: e.timestamp)
        if warning_only:
            merged = [e for e in merged if e.event_type == "Warning"]
        if limit is not None:
            merged = merged[-limit:] if limit > 0 else []
        return merged


class _Histogram:
    """Fixed-bucket histogram state: per-bucket counts (non-cumulative in
    memory, rendered cumulatively), running sum and count."""

    __slots__ = ("buckets", "counts", "sum", "count")

    def __init__(self, buckets: Tuple[float, ...]):
        self.buckets = buckets
        self.counts = [0] * len(buckets)
        self.sum = 0.0
        self.count = 0

    def observe(self, value: float) -> None:
        self.sum += value
        self.count += 1
        for i, le in enumerate(self.buckets):
            if value <= le:
                self.counts[i] += 1
                break


class MetricsRegistry:
    """Counters/gauges/histograms labelled by experiment, Prometheus text
    format.

    Metric names mirror the reference: katib_experiment_created_total,
    katib_experiment_succeeded_total, katib_experiment_failed_total,
    katib_trial_created_total, katib_trial_succeeded_total,
    katib_trial_failed_total, katib_trial_early_stopped_total, plus running
    gauges (prometheus_metrics.go). Histograms (no reference counterpart —
    its exporter is counters/gauges only) render the full
    ``_bucket``/``_sum``/``_count`` exposition series; the tracing layer
    feeds katib_span_duration_seconds{stage=...} through observe().
    """

    # latency-shaped default buckets: 1ms .. 10min, roughly log-spaced
    DEFAULT_BUCKETS: Tuple[float, ...] = (
        0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
        1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0, 600.0,
    )

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], float] = {}
        self._gauges: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], float] = {}
        self._histograms: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], _Histogram] = {}
        self._help: Dict[str, str] = {}
        # per-scrape gauge recompute hooks: [(fn, owned gauge names), ...]
        self._collectors: List[Tuple[object, Tuple[str, ...]]] = []
        self._collector_error_logged = False

    def inc(self, name: str, value: float = 1.0, **labels: str) -> None:
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            self._counters[key] = self._counters.get(key, 0.0) + value

    def set_gauge(self, name: str, value: float, **labels: str) -> None:
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            self._gauges[key] = value

    def observe(
        self,
        name: str,
        value: float,
        buckets: Optional[Tuple[float, ...]] = None,
        **labels: str,
    ) -> None:
        """Record one histogram observation. The bucket layout is fixed by
        the first observation of a series; later ``buckets`` arguments are
        ignored (exposition series must keep a stable layout)."""
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            h = self._histograms.get(key)
            if h is None:
                h = self._histograms[key] = _Histogram(
                    tuple(buckets) if buckets else self.DEFAULT_BUCKETS
                )
            h.observe(value)

    def set_help(self, name: str, text: str) -> None:
        """One-line # HELP text for a metric name (single line; newlines
        would corrupt the exposition)."""
        with self._lock:
            self._help[name] = " ".join(str(text).split())

    @staticmethod
    def gauge_key(name: str, **labels: str) -> Tuple[str, Tuple[Tuple[str, str], ...]]:
        """Key builder for collector result dicts (see set_collector)."""
        return (name, tuple(sorted(labels.items())))

    def set_collector(self, fn, names: Tuple[str, ...] = ()) -> None:
        """Register a hook invoked at the start of every render(): the
        reference's custom-collector pattern (prometheus_metrics.go collect)
        — current-state gauges are recomputed from live state per scrape, so
        they can't go stale through any mutation path. ``fn`` returns a dict
        of ``gauge_key(...) -> value``; ``names`` declares which gauge names
        the collector owns. render() swaps every series of the owned names in
        ONE lock acquisition, so a concurrent scrape never observes a
        cleared-but-not-yet-repopulated registry, and owned series vanish
        when the collector returns none for them (deleted experiments).

        Legacy single-collector surface: REPLACES every registered hook.
        Subsystems sharing one registry (controller status gauges + the
        telemetry sampler) use :meth:`add_collector` instead."""
        with self._lock:
            self._collectors = [(fn, tuple(names))]

    def add_collector(self, fn, names: Tuple[str, ...] = ()) -> None:
        """Append a collector hook (same contract as set_collector); each
        hook owns a disjoint set of gauge names. Registration happens from
        subsystem constructors on whatever thread builds them — locked, so a
        concurrent scrape's hook iteration never sees a half-appended list."""
        with self._lock:
            self._collectors.append((fn, tuple(names)))

    def render(self) -> str:
        """Prometheus text exposition format."""
        with self._lock:
            collectors = list(self._collectors)
        if collectors:
            merged: Dict = {}
            names: set = set()
            for fn, owned in collectors:
                try:
                    collected = fn()
                except Exception:
                    # a scrape must not fail because state was mid-mutation —
                    # but a persistent collector bug must not be silent
                    # either; a failing hook's owned gauges stay frozen
                    # while the other hooks keep collecting
                    if not self._collector_error_logged:
                        self._collector_error_logged = True
                        logging.getLogger("katib_tpu.metrics").exception(
                            "gauge collector failed; its current-state gauges "
                            "frozen (logged once)"
                        )
                    continue
                if collected is None:
                    continue
                merged.update(collected)
                names |= set(owned) | {key[0] for key in collected}
            if names or merged:
                with self._lock:
                    for key in [k for k in self._gauges if k[0] in names]:
                        del self._gauges[key]
                    self._gauges.update(merged)
        lines: List[str] = []
        # O(1) dedup of the per-name metadata lines — the old
        # `lines.append(...) if ... not in lines else None` idiom was an
        # O(n²) membership scan wrapped in an expression statement
        seen: set = set()

        def _meta(name: str, kind: str) -> None:
            if name in seen:
                return
            seen.add(name)
            lines.append(f"# HELP {name} {self._help.get(name, _default_help(name, kind))}")
            lines.append(f"# TYPE {name} {kind}")

        with self._lock:
            for (name, labels), value in sorted(self._counters.items()):
                _meta(name, "counter")
                lines.append(f"{_series(name, labels)} {value}")
            for (name, labels), value in sorted(self._gauges.items()):
                _meta(name, "gauge")
                lines.append(f"{_series(name, labels)} {value}")
            for (name, labels), h in sorted(self._histograms.items()):
                _meta(name, "histogram")
                cumulative = 0
                for le, count in zip(h.buckets, h.counts):
                    cumulative += count
                    lines.append(
                        f"{_series(name + '_bucket', labels + (('le', _fmt_le(le)),))} "
                        f"{float(cumulative)}"
                    )
                lines.append(
                    f"{_series(name + '_bucket', labels + (('le', '+Inf'),))} "
                    f"{float(h.count)}"
                )
                lines.append(f"{_series(name + '_sum', labels)} {h.sum}")
                lines.append(f"{_series(name + '_count', labels)} {float(h.count)}")
        return "\n".join(lines) + "\n"


def _series(name: str, labels: Tuple[Tuple[str, str], ...]) -> str:
    """Series head; unlabelled series (the obslog pipeline counters) must
    render bare — `name{}` trips strict exposition parsers."""
    if not labels:
        return name
    return name + "{" + ",".join(f'{k}="{v}"' for k, v in labels) + "}"


def _fmt_le(le: float) -> str:
    """Prometheus-conventional bucket bound rendering: 0.005, 1, 30."""
    return f"{le:g}"


# HELP text for the katib_* catalog (docs/observability.md); names outside
# the catalog get a generated one-liner so every family still carries HELP.
_HELP_CATALOG: Dict[str, str] = {
    "katib_experiment_created_total": "Experiments created.",
    "katib_experiment_succeeded_total": "Experiments that completed successfully.",
    "katib_experiment_failed_total": "Experiments that completed failed.",
    "katib_experiment_deleted_total": "Experiments deleted.",
    "katib_experiments_current": "Experiments by current status (recomputed per scrape).",
    "katib_trial_created_total": "Trials created.",
    "katib_trial_succeeded_total": "Trials that succeeded.",
    "katib_trial_failed_total": "Trials that failed.",
    "katib_trial_killed_total": "Trials killed.",
    "katib_trial_early_stopped_total": "Trials early-stopped.",
    "katib_trial_metrics_unavailable_total": "Trials finishing without objective metrics.",
    "katib_trial_completed_total": "Trials completed (other terminal states).",
    "katib_trial_preempted_total": "Trial preemptions by the fair-share policy.",
    "katib_trials_current": "Trials by current condition (recomputed per scrape).",
    "katib_queue_depth": "Pending trials per experiment after the last dispatch pass.",
    "katib_queue_wait_seconds": "Oldest pending trial's wait per experiment.",
    "katib_fairshare_deficit": "Fair-share deficit (normalized device-seconds) per experiment.",
    "katib_pack_formed_total": "Trial packs formed (vmapped multi-trial programs).",
    "katib_trial_packed_total": "Trials dispatched as pack members.",
    "katib_pack_occupancy": "Members / capacity of the most recent pack.",
    "katib_obslog_flush_total": "Group-commit flushes of the buffered observation store.",
    "katib_obslog_flush_batch_rows": "Rows drained by buffered-store flushes.",
    "katib_obslog_flush_latency_seconds": "Latency of the last buffered-store flush.",
    "katib_obslog_buffered_rows": "Rows currently buffered in the write-behind store.",
    "katib_span_duration_seconds": "Trial lifecycle stage durations from tracing spans, by stage.",
    # resource telemetry + health watchdog (katib_tpu/telemetry.py) — the
    # TrialStalled / TrialOOMRisk warning events pair with these counters
    # and show in GET /api/events?warning=1
    "katib_telemetry_samples_total": "Per-trial resource samples recorded by the telemetry sampler.",
    "katib_trial_stalled_total": "Trials flagged by the watchdog: no report heartbeat past runtime.stall_seconds.",
    "katib_trial_oom_risk_total": "Trials whose monotonic RSS growth crossed the OOM-risk fraction of host memory.",
    "katib_trial_host_rss_bytes": "Latest sampled host RSS per running trial (/proc; in-process trials share the controller process).",
    "katib_trial_cpu_percent": "Latest sampled CPU utilization per running trial (percent of one core).",
    "katib_device_hbm_used_bytes": "Accelerator memory in use per local device (jax memory_stats).",
    "katib_xla_cache_entries": "Entries in the persistent XLA compilation cache.",
    "katib_xla_cache_bytes": "Total size of the persistent XLA compilation cache.",
    # AOT compile service (katib_tpu/compilesvc) — the CompileFailed /
    # BackendInitFailed warning events pair with these series
    "katib_compile_queue_depth": "Compile jobs queued in the AOT compile service (cost-ordered).",
    "katib_compile_cache_hit_total": "Trial submissions whose dispatch group was already warm in the executable registry.",
    "katib_compile_cache_miss_total": "Trial submissions whose dispatch group was not yet warm (pending/compiling/new/failed).",
    "katib_compile_failed_total": "AOT compiles that failed or timed out; the fingerprint group is quarantined.",
    "katib_compile_seconds": "Wall-clock of AOT compiles executed by the service, per experiment.",
    # fused population loops (katib_tpu/runtime/population.py, ISSUE 9)
    "katib_population_generations_total": "PBT/ENAS generations executed by the fused population runtime.",
    "katib_population_fused_seconds": "Wall-clock of fused population scan chunks (one compiled program per chunk).",
    # vectorized / async suggestion plane (ISSUE 10, suggest/vectorized.py
    # + controller/suggestion.py) — WarmStartApplied pairs with the
    # warm-start counter
    "katib_suggestion_batch_seconds": "Wall-clock of suggestion batch computes, by algorithm and mode (inline vs prefetch).",
    "katib_suggestion_buffer_ready_total": "Assignments served from the async prefetch buffer.",
    "katib_suggestion_buffer_miss_total": "Buffer consults that fell back to the inline compute (cold or stale buffer).",
    "katib_warm_start_total": "Experiments whose suggester was seeded from matching completed-experiment history.",
    # native multi-fidelity search (katib_tpu/controller/multifidelity.py,
    # ISSUE 11) — the RungPaused / RungPromoted / RungPruned events pair
    # with these series
    "katib_rung_promotions_total": "Rung-paused trials promoted to the next fidelity (checkpoint-resumed or re-run from scratch).",
    "katib_rung_pruned_total": "Rung-paused trials pruned when the ladder drained (outside the top 1/eta of their rung).",
    "katib_multifidelity_device_seconds": "Device-seconds consumed by multi-fidelity (asha/bohb) trial stints, charged at gang release.",
    # model-based multi-fidelity + dwell-window promotion packing (ISSUE 13)
    "katib_bracket_active": "Hyperband brackets that still hold rung-paused or dwell-pending trials, per experiment.",
    "katib_promotion_pack_size": "Size of the most recent dwell-batched promotion resubmission (rung 1+ pack seed).",
    # supervised device plane (ISSUE 12, controller/deviceplane.py) — the
    # DeviceLost / DeviceLeaseRevoked / BackendFailedOver warning events
    # pair with these series
    "katib_device_lease_granted_total": "Device leases granted by the supervised device plane (one per gang allocation).",
    "katib_device_lease_revoked_total": "Leases the plane revoked: expired zombie holds reclaimed or heartbeat-missed holders voided.",
    "katib_device_lease_active": "Leases currently in ACTIVE state (holders running on their devices).",
    "katib_device_lease_zombie": "Leases in ZOMBIE state (abandoned holders awaiting reclaim at lease expiry).",
    "katib_device_lost_total": "Devices removed from custody: probe failures, executor backend errors, chaos revocations.",
    "katib_backend_failover_total": "Whole-backend failovers (every live device lost; the fallback pool was swapped in).",
    # crash-tolerant controller (ISSUE 14, controller/recovery.py) — the
    # ControllerRecovered / LeaseTakenOver / QuiesceTimeout events pair
    # with these series
    "katib_controller_lease_renewals_total": "Heartbeat renewals of the controller's single-writer lease on the state root.",
    "katib_controller_lease_takeover_total": "Times this controller took over an expired or dead-holder lease from a previous incarnation.",
    "katib_controller_lease_age_seconds": "Seconds this controller has continuously held the state-root lease.",
    "katib_controller_lease_fence": "Monotonic fence token of the held lease (increments on every takeover).",
    "katib_recovery_replays_total": "Checkpoint-preserving restarts: load_experiment passes that replayed the recovery journal.",
    "katib_recovery_trials_resubmitted_total": "In-flight trials requeued by a recovery load (one dispatch barrier per restart).",
    "katib_recovery_rows_preserved_total": "Observation rows preserved across controller restarts (at or before the last durable checkpoint).",
    "katib_recovery_rows_truncated_total": "Un-checkpointed observation rows truncated at restart (the resumed stint re-reports them).",
    "katib_recovery_replay_seconds": "Wall-clock of the last recovery replay (journal + truncation + requeue), per experiment.",
    # sharded control plane (ISSUE 15, controller/placement.py +
    # service/httpapi.py) — the ReplicaJoined / ReplicaFailedOver events
    # pair with these series
    "katib_rpc_requests_total": "Wire-protocol requests served, by api.proto service, method and status code.",
    "katib_rpc_latency_seconds": "Wire-protocol request latency, by api.proto service (plus tenant= and method= labels when runtime.wire_tracing is on).",
    # distributed tracing & fleet plane (ISSUE 19, tracing.py + both wire
    # planes) — the TraceContextInvalid warning event pairs with these
    "katib_slo_violations_total": "Wire requests whose latency exceeded the configured per-method objective (runtime.slo_objectives), by tenant and method.",
    "katib_replica_experiments": "Experiments currently placed on each replica (placement leases held).",
    # framed ingest plane (ISSUE 16, service/ingest.py) — the binary
    # observation-streaming sibling of the JSON DBManager wire
    "katib_ingest_frames_total": "Binary observation DATA frames accepted by the framed ingest plane.",
    "katib_ingest_batch_rows": "Observation rows landed per coalesced ingest group commit.",
    "katib_ingest_coalesce_depth": "Frames merged into the most recent coalesced ingest drain.",
    # tenancy plane (ISSUE 17, service/tenancy.py) — per-tenant identity,
    # isolation and quota enforcement on both wire planes
    "katib_tenant_requests_total": "Wire requests admitted under a resolved tenant identity, by tenant.",
    "katib_tenant_denied_total": "Cross-tenant or unauthorized wire requests rejected (403 / ERR frame), by tenant and plane.",
    "katib_tenant_quota_refusals_total": "Experiment admissions refused with a tenant-tagged 429 (admission rate or max-experiments quota).",
    # step-statistics plane (ISSUE 20, runtime/stepstats.py + controller/
    # stepstats.py) — the RetraceStorm / GangStraggler / StepTimeRegression
    # warning events pair with these series
    "katib_step_seconds": "Per-experiment step-time rollup over recent stints, by quantile (p50/p95).",
    "katib_trial_throughput": "Aggregate steps per second per experiment (total steps / total step-seconds over recent stints).",
    "katib_trial_mfu_ratio": "Latest model-FLOPs-utilization per experiment (cost-model FLOPs / achieved FLOP/s over hardware peak).",
    "katib_trial_retraces_total": "Recompiles past the first compile observed by trial stints (JAX compile events), per experiment.",
    "katib_objective_per_device_second": "Best objective divided by accumulated gang device-seconds, per experiment (ROADMAP 3c admission signal).",
}


def _default_help(name: str, kind: str) -> str:
    return _HELP_CATALOG.get(name, f"katib-tpu {kind} {name}.")


# Event-reason catalog: one operator-facing line per reason recorded through
# EventRecorder.event (docs/static-analysis.md KTI302 — the analyzer fails
# the build when a literal reason is emitted without an entry, so every
# event surfaced in /api/events stays look-up-able). Reasons that reach the
# recorder through dynamic sites (trial.current_reason in
# scheduler._record_terminal, the experiment terminal reason in
# experiment._on_completed) are cataloged here too for completeness.
EVENT_CATALOG: Dict[str, str] = {
    # experiment lifecycle
    "ExperimentCreated": "Experiment admitted and persisted.",
    "ExperimentGoalReached": "Objective goal met; experiment succeeded.",
    "ExperimentMaxTrialsReached": "maxTrialCount trials finished; experiment succeeded.",
    "ExperimentMaxFailedTrialsReached": "maxFailedTrialCount exceeded; experiment failed.",
    "ExperimentSuggestionEndReached": "Suggestion algorithm reported search end.",
    "ExperimentSuggestionFailed": "Suggestion service errored; experiment failed.",
    "Succeeded": "Experiment terminal condition (no specific reason recorded).",
    "Failed": "Experiment terminal condition (no specific reason recorded).",
    # trial lifecycle
    "TrialCreated": "Trial admitted to the scheduler queue.",
    "TrialPending": "Trial waiting for its gang device allocation.",
    "TrialRunning": "Trial dispatched onto devices.",
    "TrialSucceeded": "Trial finished with the objective metric available.",
    "TrialFailed": "Trial failed (non-zero exit, exception, or failure condition).",
    "TrialKilled": "Trial killed by early stopping shrink, timeout escalation, or kill().",
    "TrialEarlyStopped": "Early-stopping rules tripped; trial stopped.",
    "MetricsUnavailable": "Trial finished without a usable objective metric.",
    "DuplicateResultReused": "Identical-assignment result copied; workload not re-run.",
    "TrialRestarting": "Failed trial requeued under max_trial_restarts.",
    "TrialResubmitted": "In-flight trial requeued after a controller restart.",
    "TrialLost": "Trial state lost across a controller restart; marked failed.",
    "SchedulerShutdown": "Trial killed because the controller shut down (resumable).",
    # scheduling / packing (PR 1-2)
    "PackFormed": "Compatible trials merged into one vmapped program.",
    "TrialDevicesClamped": "Gang request exceeded machine size; allocation clamped.",
    "TrialPreempted": "Fair-share policy preempted the trial for higher-priority work.",
    "TrialQueueStalled": "Trial pending past runtime.queue_stall_seconds.",
    # telemetry watchdog (PR 5)
    "TrialStalled": "No report() heartbeat past runtime.stall_seconds.",
    "TrialOOMRisk": "Monotonic RSS growth past runtime.oom_risk_fraction of host memory.",
    # semantic admission pre-flight (PR 7, analysis/program.py)
    "PredictedHbmNearCapacity": "Static peak-HBM estimate within the warning fraction of device memory.",
    # AOT compile service (PR 8, katib_tpu/compilesvc)
    "CompileFailed": "AOT compile failed or timed out; fingerprint quarantined, trials compile inline.",
    "BackendInitFailed": "Accelerator backend init/probe failed or hung; device probing disabled for this process.",
    # fused population loops (PR 9, katib_tpu/runtime/population.py)
    "PopulationFused": "Opted-in PBT/ENAS sweep dispatched as one fused on-device population program.",
    # vectorized suggestion plane / transfer HPO (PR 10)
    "WarmStartApplied": "Suggester seeded from completed experiments with a matching search-space signature.",
    # native multi-fidelity search (ISSUE 11, controller/multifidelity.py)
    "RungPaused": "Trial completed its rung budget and paused (checkpoint + observations intact) awaiting a promotion decision.",
    "RungPromoted": "Rung-paused trial resubmitted at the next fidelity, resuming its checkpoint (or from scratch if unusable).",
    "RungPruned": "Rung-paused trial finalized early-stopped: outside the top 1/eta of its rung when the ladder drained.",
    # model-based multi-fidelity (ISSUE 13, controller/multifidelity.py)
    "PromotionBatched": "Same-ladder promotions accumulated under the dwell window were resubmitted as one batch so rung 1+ dispatches as vmapped packs.",
    # supervised device plane (ISSUE 12, controller/deviceplane.py)
    "DeviceLost": "A device left custody (probe failure, heartbeat miss, backend error, or chaos injection); the holding gang preempts.",
    "DeviceLeaseRevoked": "The plane voided a lease: an expired zombie hold was reclaimed into the pool, or a heartbeat-missed holder was cut off.",
    "BackendFailedOver": "Every slot of an abstract pool was lost; the fallback pool was swapped in.",
    "DevicePoolExhausted": "Every device of the pool was lost and nothing can stand in for them; pending trials stay queued.",
    # crash-tolerant controller (ISSUE 14, controller/recovery.py)
    "ControllerRecovered": "A restarted controller replayed the recovery journal and requeued in-flight trials with their checkpointed observation rows preserved.",
    "LeaseTakenOver": "This controller took over the state root's single-writer lease from an expired or dead previous holder (fence token incremented).",
    "QuiesceTimeout": "The scheduler did not quiesce within its deadline after experiment completion; a zombie trial may still hold its gang allocation.",
    # sharded control plane (ISSUE 15, controller/placement.py)
    "ReplicaJoined": "A controller replica registered with the shared root's placement plane and began claiming experiments.",
    "ReplicaFailedOver": "A replica took over a dead or expired peer's experiment placement (fence bumped) and recovered it from the shared root.",
    # multi-tenant service tier (ISSUE 17, service/tenancy.py)
    "AuthDisabled": "Server started with no auth token configured: every wire request is accepted as the break-glass admin identity.",
    "TenantQuotaRefused": "An experiment admission was refused with a tenant-tagged 429 (admission rate or max-experiments quota exceeded).",
    # distributed tracing plane (ISSUE 19, tracing.py + both wire planes)
    "TraceContextInvalid": "A wire request carried a malformed or oversized traceparent (header or frame field); the context was ignored and the request served without it.",
    # step-statistics plane (ISSUE 20, controller/stepstats.py)
    "RetraceStorm": "One stint recompiled more than runtime.retrace_storm_threshold times past the first compile — the train loop is likely shape-unstable and burning its step budget on XLA retraces.",
    "GangStraggler": "A packed/fused member's p95 step time exceeded the gang median by runtime.straggler_ratio — the slowest member is pacing the shared program.",
    "StepTimeRegression": "A resumed/promoted stint's p50 step time exceeded the same trial's prior-stint baseline (persisted perf rows) by runtime.step_regression_ratio.",
}
