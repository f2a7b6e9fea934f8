"""Framework configuration — the katib-config equivalent.

reference pkg/apis/config/v1beta1/types.go:27-128 (KatibConfig:
RuntimeConfig + InitConfig + per-algorithm SuggestionConfig /
EarlyStoppingConfig / MetricsCollectorConfig, loaded from the katib-config
ConfigMap by pkg/util/v1beta1/katibconfig/config.go) and the viper flag layer
(cmd/katib-controller/v1beta1/main.go:76-104).

Here: one typed dataclass loaded from JSON file + environment overrides.
Per-algorithm config maps algorithm name -> either an import path overriding
the built-in implementation (the reference's per-algorithm container image)
or a service address to run it out-of-process over gRPC
(katib_tpu.service.rpc.RemoteSuggester — the reference's pod topology).
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

ENV_CONFIG_PATH = "KATIB_TPU_CONFIG"


@dataclass
class SuggestionConfig:
    """reference types.go SuggestionConfig (image/resources -> import path /
    service address / default settings)."""

    import_path: Optional[str] = None    # "module:ClassName" override
    service_address: Optional[str] = None  # run via gRPC instead of in-process
    default_settings: Dict[str, str] = field(default_factory=dict)


@dataclass
class EarlyStoppingConfig:
    import_path: Optional[str] = None
    default_settings: Dict[str, str] = field(default_factory=dict)


@dataclass
class RuntimeConfig:
    """reference types.go RuntimeConfig + controller flags."""

    default_parallel_trial_count: int = 3
    max_trial_restarts: int = 0            # retries for failed trials (0 = off)
    trial_timeout_seconds: Optional[float] = None
    obslog_backend: str = "auto"           # sqlite | native | memory | auto
    obslog_buffered: bool = True           # group-commit write-behind wrapper
    obslog_buffer_rows: int = 8192         # backpressure bound (buffered rows)
    tracing: bool = True                   # trial lifecycle spans (tracing.py)
    trace_ring_spans: int = 4096           # per-experiment span ring bound
    # per-trial resource telemetry + health watchdog (telemetry.py)
    telemetry: bool = True
    telemetry_interval_seconds: float = 5.0
    telemetry_ring_samples: int = 720      # per-trial sample ring bound (~1h at 5s)
    stall_seconds: float = 120.0           # TrialStalled heartbeat threshold
    oom_risk_fraction: float = 0.9         # TrialOOMRisk host-memory fraction
    # persisted-entry threshold for the shared XLA cache
    # (utils/compilation.py): 0.0 persists every compile — jax's own 1.0s
    # default skipped sub-second programs and defeated warm-start for small
    # CPU sweeps (ISSUE 8 satellite)
    xla_cache_min_compile_seconds: float = 0.0
    devices_per_host: Optional[int] = None  # cap devices visible to the allocator
    metrics_poll_interval: float = 0.1
    # fair-share scheduling (controller/fairshare.py)
    queue_stall_seconds: float = 120.0     # TrialQueueStalled warning threshold
    fairshare_aging_seconds: float = 60.0  # +1 effective priority per interval waited
    preemption_grace_seconds: float = 30.0  # preempt signal -> kill escalation
    # semantic program analysis (analysis/program.py): admission HBM
    # pre-flight, fingerprint pack grouping, compile-aware dispatch ordering
    semantic_analysis: bool = True
    device_hbm_bytes: Optional[int] = None  # per-device capacity for the
    # pre-flight; None = detect from jax memory_stats when available
    # AOT compile service (compilesvc/service.py): controller-side
    # compilation plane — fingerprint-keyed executable registry, cost-
    # ordered worker pool, compile-gated dispatch. compile_service=false /
    # KATIB_TPU_COMPILE_SERVICE=0 restores legacy dispatch byte-identically.
    compile_service: bool = True
    compile_workers: int = 2               # AOT worker pool size
    compile_gate_seconds: float = 0.0      # hold a dispatch unit up to this
    # long for its warm executable (0 = never hold; inline-compile fallback)
    compile_timeout_seconds: float = 600.0  # per-compile timeout (quarantine)
    # Fused on-device population loops (runtime/population.py): a PBT/ENAS
    # spec that opts in (algorithm setting fused/fused_generations) and
    # whose trial function exposes a population_program probe runs its
    # WHOLE sweep as one lax.scan program per gang dispatch.
    # fused_population=false / KATIB_TPU_FUSED_POPULATION=0 restores the
    # per-generation job-queue driver byte-identically.
    fused_population: bool = True
    # scan chunk length: the sweep checkpoints its carry (and honors
    # cooperative preemption) at every chunk boundary. 0 = one chunk per
    # sweep (no intermediate checkpoints).
    population_chunk_generations: int = 16
    # io_callback stream of {generation, best, median} from inside the
    # compiled scan: live `katib-tpu top` visibility plus the watchdog
    # heartbeat for chunks longer than stall_seconds. Off by default — the
    # callback is a per-generation host sync.
    population_stream_telemetry: bool = False
    # Vectorized suggestion plane (suggest/vectorized.py, ISSUE 10): the
    # TPE/CMA-ES/BO hot kernels run as batched jitted programs.
    # vector_suggest=false / KATIB_TPU_VECTOR_SUGGEST=0 restores the
    # legacy NumPy suggesters byte-identically.
    vector_suggest: bool = True
    # Async pipelined suggestion (controller/suggestion.py): a background
    # worker precomputes the next batch per experiment so scheduler
    # dispatch consults a ready buffer instead of blocking inline. Opt-in:
    # precomputed batches may lag the freshest completion by one pipeline
    # step (the constant-liar staleness model).
    async_suggest: bool = False
    # Precomputed assignments beyond the predicted request; 0 = the
    # experiment's parallel_trial_count.
    suggest_readahead: int = 0
    # Cross-experiment warm start (transfer HPO): seed TPE/BO priors and
    # the CMA-ES mean from completed experiments with a matching
    # search-space + objective signature. Opt-in.
    warm_start: bool = False
    warm_start_max_points: int = 256  # cap on transferred observations
    # Supervised device plane (controller/deviceplane.py, ISSUE 12):
    # device sets as leased, revocable resources — zombie-lease reclaim,
    # device-loss-as-preemption, backend failover, chaos injection hooks.
    # device_plane=false / KATIB_TPU_DEVICE_PLANE=0 restores the legacy
    # free-list allocator byte-identically.
    device_plane: bool = True
    # timeout of the periodic re-probe of a backend that already came up
    device_probe_timeout_seconds: float = 15.0
    # periodic backend re-probe on the supervisor tick; 0 = off (probe
    # only at acquisition)
    device_reprobe_interval_seconds: float = 0.0
    # zombie lease TTL: devices held by an abandoned trial are reclaimed
    # into the pool this many seconds after the kill-grace abandon
    device_lease_seconds: float = 60.0
    # lease heartbeat timeout: an ACTIVE lease with no ctx.report heartbeat
    # for this long is revoked (holder presumed dead). 0 = off — the
    # telemetry stall watchdog already covers slow-but-alive trials.
    device_heartbeat_timeout_seconds: float = 0.0
    # CPU fallback pool when the whole backend dies (a sweep degrades
    # instead of dying); false pins the sweep to the original backend
    device_failover: bool = True
    # Native multi-fidelity search (controller/multifidelity.py): ASHA
    # rung ladders as a scheduler citizen — trials pause at rung
    # boundaries with checkpoint + observations intact, survivors resume
    # at the next fidelity. Only experiments declaring `algorithm: asha`
    # use it; multifidelity=false / KATIB_TPU_MULTIFIDELITY=0 removes the
    # engine entirely (asha specs are then rejected at admission) and
    # leaves the legacy stateless hyperband path byte-identical.
    multifidelity: bool = True
    # Dwell-window promotion packing (ISSUE 13): same-rung promotions
    # accumulate for up to this many seconds and are resubmitted under one
    # dispatch barrier, so rung 1+ dispatches as vmapped packs instead of
    # trickling out one trial at a time. A drain rule flushes immediately
    # when nothing is running (the last stragglers never wait out the
    # window). 0 (default) = promotions submit at the decision point,
    # byte-identical to the PR 11 behavior.
    promotion_dwell_seconds: float = 0.0
    # Crash-tolerant controller (controller/recovery.py, ISSUE 14): the
    # recovery journal, the lease-fenced single-writer on the state root,
    # and checkpoint-preserving load_experiment (truncate the observation
    # log to the last durable checkpoint instead of dropping it).
    # recovery=false / KATIB_TPU_RECOVERY=0 constructs nothing and restores
    # the pre-recovery load_experiment behavior byte-identically.
    recovery: bool = True
    # controller lease TTL: a successor may take over this many seconds
    # after the last heartbeat (immediately when the holder pid is dead)
    controller_lease_seconds: float = 15.0
    # standby mode: a second controller on a held state root waits for the
    # lease to expire and takes over instead of refusing to start
    controller_lease_standby: bool = False
    # Sharded control plane (controller/placement.py + service/httpapi.py,
    # ISSUE 15): >0 puts the controller in replica mode — per-experiment
    # placement leases under <root>/placement/ replace the root-wide
    # single-writer lease, the journal moves to a per-replica subdir, and
    # N replica processes share one root, each owning a disjoint experiment
    # set. 0 (default / KATIB_TPU_REPLICAS unset) is byte-identical to the
    # single-controller PR 14 behavior.
    replicas: int = 0
    # experiments one replica claims at most (the placement target; the
    # failover scan also honors it when absorbing a dead replica's work)
    replica_capacity: int = 8
    # HTTP/JSON wire-protocol port per replica (0 = ephemeral, printed by
    # the replica process at start)
    rpc_port: int = 0
    # placement lease TTL: a dead replica's experiments are takeable this
    # many seconds after its last heartbeat (immediately when the holder
    # pid is dead on the same host)
    placement_lease_seconds: float = 10.0
    # -- framed ingest plane (service/ingest.py, ISSUE 16): when True each
    # replica opens a sibling binary-framed ingest port for observation
    # streaming (N trial sockets on one selectors loop, frames coalesced
    # into one group commit) and exports KATIB_TPU_INGEST_ADDR to trial
    # subprocesses. False (default) is byte-identical to the PR 15
    # JSON-only wire.
    ingest_framed: bool = False
    # framed ingest port per replica (0 = ephemeral, printed in the replica
    # ready line and surfaced via the placement registry)
    ingest_port: int = 0
    # coalescing window: a drain waits at most this long for more frames
    # before committing the pending batch (also drains on quiescence or on
    # reaching ingest_coalesce_rows, whichever comes first)
    ingest_coalesce_window_seconds: float = 0.005
    # row-count bound that forces a drain regardless of the window
    ingest_coalesce_rows: int = 4096
    # -- tenancy plane (service/tenancy.py, ISSUE 17): when True each
    # replica binds a TenantRegistry (<root>/tenants/) and both wire
    # planes resolve every request/HELLO to a tenant identity, enforce
    # namespace isolation and per-tenant quotas. False (default) is
    # byte-identical to the single-tenant plane.
    tenancy: bool = False
    # -- distributed tracing plane (tracing.py + both wire planes, ISSUE
    # 19): when True, W3C-style traceparent rides every POST /rpc/<Method>
    # (X-Katib-Traceparent header) and framed ingest DATA frame, server
    # side opens rpc/ingest/placement spans, and every completed span is
    # appended durably under <root>/traces/wire/ keyed by trace id so
    # cross-replica trees merge even after a replica SIGKILL. False
    # (default) is byte-identical wire bytes and span set to the PR 17
    # plane (asserted by a seeded on-vs-off test).
    wire_tracing: bool = False
    # per-method RPC latency objectives for the per-tenant SLO counter
    # (katib_slo_violations_total): "default=0.5,CreateExperiment=2.0"
    # seconds; empty = no objectives, the counter never increments
    slo_objectives: str = ""
    # slow-RPC flight recorder: the worst N requests (by latency) kept with
    # their span trees, dumpable via GET /api/fleet/slow and SIGUSR2.
    # 0 = recorder off even when wire_tracing is on.
    slow_rpc_ring: int = 32
    # Postgres DSN for the pluggable observation store (db/dialects.py);
    # unset keeps the SQLite dialect. Requires a Postgres driver
    # (psycopg2/pg8000) in the environment.
    pg_dsn: Optional[str] = None
    # -- step-statistics plane (runtime/stepstats.py + controller/
    # stepstats.py, ISSUE 20): when True every trial context carries a step
    # clock — per-step wall durations, steps/sec, optional examples/tokens
    # throughput, retrace counters off JAX's compile events — flushed
    # through the observation pipeline under the reserved katib-tpu/perf/
    # namespace, rolled up per experiment on /metrics, and watched by the
    # RetraceStorm / GangStraggler / StepTimeRegression detectors. False
    # (default) is byte-identical wire, span set, /metrics, and observation
    # rows (asserted by a seeded on-vs-off test).
    step_stats: bool = False
    # perf window size: the step clock flushes one summary row set every
    # this many steps (mean/p95 step seconds, steps/sec, throughput)
    step_stats_flush_steps: int = 32
    # RetraceStorm: warning event when one stint re-compiles more than this
    # many times after the first compile
    retrace_storm_threshold: int = 8
    # GangStraggler: warning event when a packed/fused member's p95 step
    # time exceeds the gang median p95 by this ratio
    straggler_ratio: float = 2.0
    # StepTimeRegression: warning event when a resumed/promoted stint's p50
    # step time exceeds the same trial's prior-stint baseline by this ratio
    step_regression_ratio: float = 1.5


# Every RuntimeConfig knob is overridable from the environment without
# shipping a config file (reference: env trumps config, consts/const.go:
# 93-103). The table is DECLARATIVE and complete by construction — the
# KTI303 analyzer rule (katib_tpu/analysis) fails the build when a new
# field lands without an entry. Names follow KATIB_TPU_<FIELD>; the two
# historical exceptions keep their documented spellings.
ENV_OVERRIDES: Dict[str, str] = {
    "default_parallel_trial_count": "KATIB_TPU_DEFAULT_PARALLEL_TRIAL_COUNT",
    "max_trial_restarts": "KATIB_TPU_MAX_TRIAL_RESTARTS",
    "trial_timeout_seconds": "KATIB_TPU_TRIAL_TIMEOUT_SECONDS",
    "obslog_backend": "KATIB_TPU_OBSLOG_BACKEND",
    "obslog_buffered": "KATIB_TPU_OBSLOG_BUFFERED",
    "obslog_buffer_rows": "KATIB_TPU_OBSLOG_BUFFER_ROWS",
    "tracing": "KATIB_TPU_TRACING",
    "trace_ring_spans": "KATIB_TPU_TRACE_RING_SPANS",
    "telemetry": "KATIB_TPU_TELEMETRY",
    "telemetry_interval_seconds": "KATIB_TPU_TELEMETRY_INTERVAL_SECONDS",
    "telemetry_ring_samples": "KATIB_TPU_TELEMETRY_RING_SAMPLES",
    "stall_seconds": "KATIB_TPU_STALL_SECONDS",
    "oom_risk_fraction": "KATIB_TPU_OOM_RISK_FRACTION",
    "xla_cache_min_compile_seconds": "KATIB_TPU_XLA_CACHE_MIN_COMPILE_SECONDS",
    "devices_per_host": "KATIB_TPU_DEVICES_PER_HOST",
    "metrics_poll_interval": "KATIB_TPU_METRICS_POLL_INTERVAL",
    "queue_stall_seconds": "KATIB_TPU_QUEUE_STALL_SECONDS",
    "fairshare_aging_seconds": "KATIB_TPU_FAIRSHARE_AGING_SECONDS",
    "preemption_grace_seconds": "KATIB_TPU_PREEMPTION_GRACE_SECONDS",
    "semantic_analysis": "KATIB_TPU_SEMANTIC_ANALYSIS",
    "device_hbm_bytes": "KATIB_TPU_DEVICE_HBM_BYTES",
    "compile_service": "KATIB_TPU_COMPILE_SERVICE",
    "compile_workers": "KATIB_TPU_COMPILE_WORKERS",
    "compile_gate_seconds": "KATIB_TPU_COMPILE_GATE_SECONDS",
    "compile_timeout_seconds": "KATIB_TPU_COMPILE_TIMEOUT_SECONDS",
    "fused_population": "KATIB_TPU_FUSED_POPULATION",
    "population_chunk_generations": "KATIB_TPU_POPULATION_CHUNK_GENERATIONS",
    "population_stream_telemetry": "KATIB_TPU_POPULATION_STREAM_TELEMETRY",
    "vector_suggest": "KATIB_TPU_VECTOR_SUGGEST",
    "async_suggest": "KATIB_TPU_ASYNC_SUGGEST",
    "suggest_readahead": "KATIB_TPU_SUGGEST_READAHEAD",
    "warm_start": "KATIB_TPU_WARM_START",
    "warm_start_max_points": "KATIB_TPU_WARM_START_MAX_POINTS",
    "multifidelity": "KATIB_TPU_MULTIFIDELITY",
    "promotion_dwell_seconds": "KATIB_TPU_PROMOTION_DWELL_SECONDS",
    "recovery": "KATIB_TPU_RECOVERY",
    "controller_lease_seconds": "KATIB_TPU_CONTROLLER_LEASE_SECONDS",
    "controller_lease_standby": "KATIB_TPU_CONTROLLER_LEASE_STANDBY",
    "replicas": "KATIB_TPU_REPLICAS",
    "replica_capacity": "KATIB_TPU_REPLICA_CAPACITY",
    "rpc_port": "KATIB_TPU_RPC_PORT",
    "placement_lease_seconds": "KATIB_TPU_PLACEMENT_LEASE_SECONDS",
    "ingest_framed": "KATIB_TPU_INGEST_FRAMED",
    "ingest_port": "KATIB_TPU_INGEST_PORT",
    "ingest_coalesce_window_seconds": "KATIB_TPU_INGEST_COALESCE_WINDOW_SECONDS",
    "ingest_coalesce_rows": "KATIB_TPU_INGEST_COALESCE_ROWS",
    "device_plane": "KATIB_TPU_DEVICE_PLANE",
    "device_probe_timeout_seconds": "KATIB_TPU_DEVICE_PROBE_TIMEOUT_SECONDS",
    "device_reprobe_interval_seconds": "KATIB_TPU_DEVICE_REPROBE_INTERVAL_SECONDS",
    "device_lease_seconds": "KATIB_TPU_DEVICE_LEASE_SECONDS",
    "device_heartbeat_timeout_seconds": "KATIB_TPU_DEVICE_HEARTBEAT_TIMEOUT_SECONDS",
    "device_failover": "KATIB_TPU_DEVICE_FAILOVER",
    "tenancy": "KATIB_TPU_TENANCY",
    "wire_tracing": "KATIB_TPU_WIRE_TRACING",
    "slo_objectives": "KATIB_TPU_SLO_OBJECTIVES",
    "slow_rpc_ring": "KATIB_TPU_SLOW_RPC_RING",
    "pg_dsn": "KATIB_TPU_PG_DSN",
    "step_stats": "KATIB_TPU_STEP_STATS",
    "step_stats_flush_steps": "KATIB_TPU_STEP_STATS_FLUSH_STEPS",
    "retrace_storm_threshold": "KATIB_TPU_RETRACE_STORM_THRESHOLD",
    "straggler_ratio": "KATIB_TPU_STRAGGLER_RATIO",
    "step_regression_ratio": "KATIB_TPU_STEP_REGRESSION_RATIO",
}

_FALSY = ("0", "false", "off")


def _coerce_env(field_type: str, raw: str):
    """Parse one env value per the dataclass field's annotation (a string —
    this module uses postponed annotations). Returns (ok, value); a
    malformed number is rejected so a typo'd env var keeps the default
    loudly rather than crashing the controller at import."""
    if "Optional" in field_type and raw.lower() in ("none", "null"):
        return True, None
    if "bool" in field_type:
        return True, raw.lower() not in _FALSY
    try:
        if "int" in field_type:
            return True, int(raw)
        if "float" in field_type:
            return True, float(raw)
    except ValueError:
        return False, None
    return True, raw


@dataclass
class KatibConfig:
    runtime: RuntimeConfig = field(default_factory=RuntimeConfig)
    suggestions: Dict[str, SuggestionConfig] = field(default_factory=dict)
    early_stopping: Dict[str, EarlyStoppingConfig] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "KatibConfig":
        cfg = cls()
        r = d.get("runtime", {})
        for f in dataclasses.fields(RuntimeConfig):
            if f.name in r:
                setattr(cfg.runtime, f.name, r[f.name])
        for name, sd in d.get("suggestions", {}).items():
            cfg.suggestions[name] = SuggestionConfig(
                import_path=sd.get("importPath"),
                service_address=sd.get("serviceAddress"),
                default_settings=dict(sd.get("defaultSettings", {})),
            )
        for name, ed in d.get("earlyStopping", {}).items():
            cfg.early_stopping[name] = EarlyStoppingConfig(
                import_path=ed.get("importPath"),
                default_settings=dict(ed.get("defaultSettings", {})),
            )
        return cfg

    def to_dict(self) -> Dict[str, Any]:
        return {
            "runtime": dataclasses.asdict(self.runtime),
            "suggestions": {
                k: {
                    "importPath": v.import_path,
                    "serviceAddress": v.service_address,
                    "defaultSettings": v.default_settings,
                }
                for k, v in self.suggestions.items()
            },
            "earlyStopping": {
                k: {"importPath": v.import_path, "defaultSettings": v.default_settings}
                for k, v in self.early_stopping.items()
            },
        }


def load_config(path: Optional[str] = None) -> KatibConfig:
    """File -> env overrides, mirroring the reader + viper layering."""
    path = path or os.environ.get(ENV_CONFIG_PATH)
    cfg = KatibConfig()
    if path and os.path.exists(path):
        with open(path) as f:
            cfg = KatibConfig.from_dict(json.load(f))
    # env overrides (reference: env vars trump config, consts/const.go:93-103)
    # — driven entirely by the ENV_OVERRIDES table so every knob, present
    # and future, has the same spelling and coercion rules
    types = {f.name: str(f.type) for f in dataclasses.fields(RuntimeConfig)}
    for field_name, env_name in ENV_OVERRIDES.items():
        raw = os.environ.get(env_name)
        if raw is None or raw == "" or field_name not in types:
            continue
        ok, value = _coerce_env(types[field_name], raw)
        if ok:
            setattr(cfg.runtime, field_name, value)
        else:
            logging.getLogger("katib_tpu.config").warning(
                "ignoring malformed %s=%r (expected %s)",
                env_name, raw, types[field_name],
            )
    return cfg
