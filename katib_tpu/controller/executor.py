"""Trial executors — run one trial to completion.

Replaces the reference's trial-job execution plane (trial controller creating
K8s jobs + webhook-injected metrics sidecar, SURVEY.md §3.3) with two direct
execution paths:

- InProcessExecutor: resolves the trial template's entry point / function and
  calls it under the trial's device allocation. The TPU-native fast path — no
  pod/process startup, metrics are pushed straight into the store, and the
  early-stopping monitor raises inside the training loop.
- SubprocessExecutor: renders the command template
  (``${trialParameters.X}`` substitution — manifest/generator.go:99-186),
  spawns the process with the metrics env binding, tails its stdout applying
  early-stopping rules exactly like the reference sidecar (kill on trip), and
  parses TEXT/JSON metric lines into the store on completion
  (file-metricscollector semantics).
"""

from __future__ import annotations

import importlib
import logging
import os
import re
import signal
import subprocess
import threading
import time
import traceback
from dataclasses import dataclass
from enum import Enum
from typing import Any, Callable, Dict, List, Optional, Sequence

from ..api.spec import CollectorKind, ExperimentSpec, TrialTemplate
from ..api.status import Experiment, Trial
from ..db.store import MetricLog, ObservationStore
from ..runtime.context import TrialContext
from ..runtime.metrics import (
    ENV_DB_PATH,
    ENV_METRICS_FILE,
    ENV_TRIAL_NAME,
    EarlyStopped,
    EarlyStoppingMonitor,
    TrialKilled,
    TrialPreempted,
    parse_json_lines,
    parse_text_lines,
    set_current_reporter,
)

log = logging.getLogger("katib_tpu.executor")

# placeholder grammar is shared with spec validation so the two can't drift
from ..api.validation import META_PARAM_RE as META_RE, TRIAL_PARAM_RE


class TrialOutcome(str, Enum):
    COMPLETED = "completed"       # process/function finished cleanly
    EARLY_STOPPED = "early_stopped"
    FAILED = "failed"
    KILLED = "killed"
    PREEMPTED = "preempted"       # yielded devices to higher-priority work


@dataclass
class ExecutionResult:
    outcome: TrialOutcome
    message: str = ""
    # terminal state exposed to trial success/failure condition expressions
    # (controller/conditions.py; reference job_util.go:59-120)
    exit_code: Optional[int] = None
    stdout_path: Optional[str] = None


def render_command(template: TrialTemplate, trial: Trial) -> List[str]:
    """Placeholder substitution, mirroring applyParameters
    (manifest/generator.go:99-186): ${trialParameters.X} resolves through the
    trialParameters reference list to the assignment value; ${trialSpec.*}
    meta placeholders resolve to trial metadata."""
    assignments = trial.assignments_dict()
    ref_by_name = {tp.name: tp.reference for tp in template.trial_parameters}

    def sub_param(m: re.Match) -> str:
        name = m.group(1)
        ref = ref_by_name.get(name, name)
        if ref in assignments:
            return assignments[ref]
        if name in assignments:
            return assignments[name]
        raise KeyError(f"unresolved trial parameter placeholder {name!r}")

    def sub_meta(m: re.Match) -> str:
        key = m.group(1)
        if key == "Name":
            return trial.name
        if key == "Namespace":
            return trial.experiment_name
        if key.startswith("Labels["):
            return trial.labels.get(key[len("Labels[") : -1], "")
        if key.startswith("Annotations["):
            return ""
        return ""

    out = []
    for arg in template.command or []:
        arg = TRIAL_PARAM_RE.sub(sub_param, arg)
        arg = META_RE.sub(sub_meta, arg)
        out.append(arg)
    return out


def resolve_entry_point(template: TrialTemplate) -> Callable[..., Any]:
    if template.function is not None:
        return template.function
    assert template.entry_point is not None
    mod_name, _, fn_name = template.entry_point.partition(":")
    if not fn_name:
        raise ValueError(f"entryPoint {template.entry_point!r} must be 'module:function'")
    mod = importlib.import_module(mod_name)
    return getattr(mod, fn_name)


class TrialExecution:
    """Handle for one running trial; kill() requests termination, preempt()
    requests a cooperative checkpoint-and-yield (fair-share scheduling)."""

    def __init__(self) -> None:
        self._kill_requested = threading.Event()
        self._preempt_requested = threading.Event()

    def kill(self) -> None:
        self._kill_requested.set()

    def preempt(self) -> None:
        self._preempt_requested.set()

    @property
    def kill_requested(self) -> bool:
        return self._kill_requested.is_set()

    @property
    def kill_event(self) -> threading.Event:
        return self._kill_requested

    @property
    def preempt_requested(self) -> bool:
        return self._preempt_requested.is_set()

    @property
    def preempt_event(self) -> threading.Event:
        return self._preempt_requested


class InProcessExecutor:
    def __init__(self, obs_store: ObservationStore):
        self.obs_store = obs_store
        self._cache_enabled = False

    def execute(
        self, exp: Experiment, trial: Trial, ctx: TrialContext, handle: TrialExecution
    ) -> ExecutionResult:
        if not self._cache_enabled:
            # Shared XLA compile cache across trials — enabled lazily here so
            # read-only CLI paths never pay the JAX import.
            self._cache_enabled = True
            try:
                from ..utils.compilation import enable_compilation_cache

                enable_compilation_cache()
            except Exception:
                pass
        fn = resolve_entry_point(exp.spec.trial_template)
        token = set_current_reporter(ctx.reporter)
        ctx._trace_fn_start()  # compile boundary: first report closes it
        try:
            result = fn(ctx.assignments, ctx)
            # convenience: a returned dict of floats is auto-reported
            if isinstance(result, dict):
                numeric = {
                    k: v for k, v in result.items() if isinstance(v, (int, float))
                }
                if numeric:
                    ctx.reporter.report(**numeric)
            if ctx.reporter.stopped:
                return ExecutionResult(TrialOutcome.EARLY_STOPPED)
            if handle.kill_requested:
                return ExecutionResult(TrialOutcome.KILLED, "kill requested")
            return ExecutionResult(TrialOutcome.COMPLETED, exit_code=0)
        except EarlyStopped:
            return ExecutionResult(TrialOutcome.EARLY_STOPPED)
        except TrialKilled:
            return ExecutionResult(TrialOutcome.KILLED, "kill requested")
        except TrialPreempted:
            return ExecutionResult(
                TrialOutcome.PREEMPTED, "preempted by higher-priority work"
            )
        except Exception:
            return ExecutionResult(
                TrialOutcome.FAILED, traceback.format_exc(limit=10), exit_code=1
            )
        finally:
            ctx._trace_fn_end()
            from ..runtime import metrics as _m

            _m._current_reporter.reset(token)


_port_lock = threading.Lock()
_recent_ports: Dict[int, float] = {}  # port -> issued-at (avoid concurrent reuse)


def _free_port() -> int:
    """Free localhost port for a gang coordinator. The probe socket must close
    before a worker can bind the port, so cross-process TOCTOU is inherent —
    but the common collision (two concurrent gang trials in THIS controller
    getting the same port) is prevented by tracking recently-issued ports."""
    import socket

    with _port_lock:
        now = time.time()
        for p in [p for p, t in _recent_ports.items() if now - t > 60.0]:
            del _recent_ports[p]
        for _ in range(16):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
            s.close()
            if port not in _recent_ports:
                _recent_ports[port] = now
                return port
        _recent_ports[port] = now  # every probe collided: accept the last
        return port


class _AdaptivePoll:
    """Adaptive sleep for the subprocess wait loops: the base interval while
    the trial shows signs of life (process exit checks stay cheap), doubling
    toward a 1s ceiling once the trial has been quiet — no exit, no tailed
    metric lines, no fresh scrape rows — for ``backoff_after`` seconds. A
    long-running silent trial shouldn't cost the controller 10 wakeups/sec
    per trial. ``adaptive=False`` (an explicit poll_interval override) pins
    the base interval."""

    def __init__(
        self,
        base: float,
        backoff_after: float = 30.0,
        maximum: float = 1.0,
        adaptive: bool = True,
    ):
        self.base = base
        self.backoff_after = backoff_after
        self.maximum = max(maximum, base)
        self.adaptive = adaptive
        self._quiet_since = time.time()
        self._delay = base

    def activity(self, now: Optional[float] = None) -> None:
        self._quiet_since = time.time() if now is None else now
        self._delay = self.base

    def next_delay(self, now: Optional[float] = None) -> float:
        if not self.adaptive:
            return self.base
        now = time.time() if now is None else now
        if now - self._quiet_since < self.backoff_after:
            return self.base
        self._delay = min(self._delay * 2, self.maximum)
        return self._delay


class SubprocessExecutor:
    POLL_INTERVAL = 0.1
    POLL_BACKOFF_AFTER = 30.0  # seconds of quiet before backoff engages
    POLL_BACKOFF_MAX = 1.0     # backoff ceiling

    def __init__(self, obs_store: ObservationStore, db_path: Optional[str] = None):
        self.obs_store = obs_store
        self.db_path = db_path  # lets subprocesses push via env binding

    def execute(
        self, exp: Experiment, trial: Trial, ctx: TrialContext, handle: TrialExecution
    ) -> ExecutionResult:
        spec = exp.spec
        cmd = render_command(spec.trial_template, trial)
        workdir = ctx.workdir or os.getcwd()
        os.makedirs(workdir, exist_ok=True)
        stdout_path = os.path.join(workdir, "stdout.log")

        env = dict(os.environ)
        env.update(spec.trial_template.env)
        env[ENV_TRIAL_NAME] = trial.name
        if self.db_path:
            env[ENV_DB_PATH] = self.db_path
        self._stamp_profile_env(env)
        if ctx.trace_id and ctx.trace_parent:
            # W3C-traceparent-style context: the child's report_metrics spans
            # rejoin this trial's controller trace (katib_tpu.tracing)
            from ..tracing import ENV_TRACEPARENT, format_traceparent

            env[ENV_TRACEPARENT] = format_traceparent(ctx.trace_id, ctx.trace_parent)
            env.setdefault("KATIB_TPU_EXPERIMENT", trial.experiment_name)
        metrics_file = None
        mc = spec.metrics_collector_spec
        if mc.collector_kind == CollectorKind.FILE and mc.source and mc.source.file_path:
            metrics_file = mc.source.file_path
            if not os.path.isabs(metrics_file):
                metrics_file = os.path.join(workdir, metrics_file)
            env[ENV_METRICS_FILE] = metrics_file

        monitor = None
        if trial.early_stopping_rules:
            monitor = EarlyStoppingMonitor(
                trial.early_stopping_rules,
                spec.objective.objective_metric_name,
                spec.objective.type,
            )

        prom_logs: List[MetricLog] = []
        with open(stdout_path, "wb") as out:
            proc = subprocess.Popen(
                cmd,
                stdout=out,
                stderr=subprocess.STDOUT,
                env=env,
                cwd=spec.trial_template.working_dir or workdir,
                start_new_session=True,
            )
            # crash fencing (controller/recovery.py): record the child's
            # pid (== its session/pgid) so a controller restarted after a
            # SIGKILL can fence this orphan before re-running the trial
            from .recovery import clear_pidfile, write_pidfile

            write_pidfile(workdir, proc.pid)
            if ctx.on_subprocess is not None:
                # telemetry: /proc sampling follows the child, not this process
                ctx.on_subprocess([proc.pid])
            try:
                outcome = self._wait(
                    proc, stdout_path, metrics_file, monitor, spec, handle,
                    prom_logs, heartbeat=ctx.on_report,
                )
            finally:
                clear_pidfile(workdir)
        if prom_logs:
            self.obs_store.report_observation_log(trial.name, prom_logs)

        # Collect metrics from the produced output (sidecar CollectObservationLog).
        self._collect(trial, stdout_path, metrics_file, spec)
        # Drain cross-process pushed metrics into the controller's store when
        # they live in different backends (subprocesses always push to the
        # SQLite file at db_path; the controller may use the native engine).
        self._drain_pushed(trial)

        if outcome is not None:
            outcome.exit_code = proc.returncode
            outcome.stdout_path = stdout_path
            return outcome
        if proc.returncode == 0:
            return ExecutionResult(
                TrialOutcome.COMPLETED, exit_code=0, stdout_path=stdout_path
            )
        from ..telemetry import OOM_KILL_MESSAGE, oom_kill_suspected

        # an uninstructed SIGKILL death (the kill path returned above, so
        # nobody in THIS controller sent it) is the kernel OOM killer's
        # signature — classify it instead of reporting a bare exit code
        message = (
            OOM_KILL_MESSAGE
            if oom_kill_suspected(proc.returncode)
            else f"process exited with code {proc.returncode}"
        )
        from ..utils.backend import holds_accelerator

        if holds_accelerator() and not env.get("JAX_PLATFORMS", "").startswith("cpu"):
            message += (
                "; note: this controller process has initialized the "
                "accelerator backend and owns the chip — a trial subprocess "
                "that needs the chip cannot have it (see README, 'Who owns "
                "the chip')"
            )
        return ExecutionResult(
            TrialOutcome.FAILED,
            message,
            exit_code=proc.returncode,
            stdout_path=stdout_path,
        )

    @staticmethod
    def _stamp_profile_env(env: Dict[str, str]) -> None:
        """Honor $KATIB_TPU_PROFILE end-to-end: the controller's setting is
        stamped onto trial subprocesses (unless the trial template pinned its
        own), and ctx.profile()/profile_trace default from it."""
        from ..runtime.profiling import ENV_PROFILE

        if ENV_PROFILE in os.environ:
            env.setdefault(ENV_PROFILE, os.environ[ENV_PROFILE])

    SCRAPE_INTERVAL = 1.0  # seconds between Prometheus scrapes
    # A metric legitimately reporting the SAME value across steps must still
    # produce observations (early-stopping step counters advance per record):
    # identical values are deduped only within this window, then re-recorded.
    SCRAPE_DEDUP_WINDOW = 10.0

    def _scrape_prometheus(
        self, spec: ExperimentSpec, prom_logs: List[MetricLog],
        monitor: Optional[EarlyStoppingMonitor], last_scraped: Dict[str, Any],
    ) -> Optional[ExecutionResult]:
        from urllib.request import urlopen

        from ..runtime.metrics import parse_prometheus_text

        src = spec.metrics_collector_spec.source
        url = f"http://{src.http_host}:{src.http_port}{src.http_path}"
        try:
            with urlopen(url, timeout=2) as resp:
                text = resp.read().decode(errors="replace")
        except Exception:
            # endpoint not up (yet), mid-shutdown, or speaking non-HTTP —
            # skip this scrape and keep polling (urllib raises OSError,
            # http.client.* and ValueError variants here)
            return None
        logs = parse_prometheus_text(text, spec.objective.all_metric_names())
        # scrapes sample state, they are not reports: dedup on (value, time
        # bucket) — a changed value records immediately, an unchanged value
        # re-records after SCRAPE_DEDUP_WINDOW so constant metrics still
        # advance the observation log / early-stopping step counters
        now = time.time()
        fresh = []
        for log in logs:
            prev = last_scraped.get(log.metric_name)
            if prev is not None and prev[0] == log.value and now - prev[1] < self.SCRAPE_DEDUP_WINDOW:
                continue
            last_scraped[log.metric_name] = (log.value, now)
            fresh.append(log)
        prom_logs.extend(fresh)
        if monitor is not None:
            for log in fresh:
                try:
                    value = float(log.value)
                except ValueError:
                    continue
                if monitor.observe(log.metric_name, value):
                    return ExecutionResult(TrialOutcome.EARLY_STOPPED)
        return None

    def _wait(
        self,
        proc: subprocess.Popen,
        stdout_path: str,
        metrics_file: Optional[str],
        monitor: Optional[EarlyStoppingMonitor],
        spec: ExperimentSpec,
        handle: TrialExecution,
        prom_logs: Optional[List[MetricLog]] = None,
        heartbeat: Optional[Callable[[], None]] = None,
    ) -> Optional[ExecutionResult]:
        """Poll for exit; tail output applying stop rules (the reference
        sidecar's watchMetricsFile loop); scrape the trial's Prometheus
        endpoint when the collector kind asks for it. The poll interval
        adapts: 0.1s while the trial emits output/metrics, backing off
        exponentially to 1s after 30s of quiet (see _AdaptivePoll).
        ``heartbeat`` is the telemetry watchdog's liveness hook — a
        subprocess trial can't call ctx.report(), so tailed metric lines
        and fresh scrape rows count as its heartbeats instead."""
        watch_path = metrics_file or stdout_path
        scrape = (
            spec.metrics_collector_spec.collector_kind == CollectorKind.PROMETHEUS
            and spec.metrics_collector_spec.source is not None
            and prom_logs is not None
        )
        last_scrape = 0.0
        last_scraped: Dict[str, Any] = {}  # metric -> (value, recorded_at)
        tailer = self._make_stop_tailer(spec, watch_path) if monitor else None
        poll = self._make_poll()
        try:
            while True:
                if handle.kill_requested:
                    self._terminate(proc)
                    return ExecutionResult(TrialOutcome.KILLED, "kill requested")
                rc = proc.poll()
                if scrape and time.time() - last_scrape >= self.SCRAPE_INTERVAL:
                    last_scrape = time.time()
                    before = len(prom_logs)
                    stopped = self._scrape_prometheus(spec, prom_logs, monitor, last_scraped)
                    if len(prom_logs) > before:
                        poll.activity()
                        if heartbeat is not None:
                            heartbeat()
                    if stopped is not None:
                        self._terminate(proc)
                        return stopped
                if tailer is not None:
                    parsed = tailer.poll()
                    if parsed:
                        poll.activity()
                        if heartbeat is not None:
                            heartbeat()
                    for name, raw, _idx in parsed:
                        try:
                            value = float(raw)
                        except ValueError:
                            continue  # skip unparseable values like fold_observation
                        if monitor.observe(name, value):
                            self._terminate(proc)
                            return ExecutionResult(TrialOutcome.EARLY_STOPPED)
                if rc is not None:
                    if scrape:
                        # best-effort final scrape — values published within the
                        # last SCRAPE_INTERVAL are otherwise lost when the trial's
                        # endpoint dies with the process. (PROMETHEUS trials that
                        # exit immediately after publishing should also Push — see
                        # README metrics-collector notes.)
                        self._scrape_prometheus(spec, prom_logs, monitor, last_scraped)
                    return None
                time.sleep(poll.next_delay())
        finally:
            if tailer is not None:
                tailer.close()

    def _make_poll(self) -> _AdaptivePoll:
        # an explicit poll_interval override (KatibConfig
        # metrics_poll_interval — the scheduler sets the INSTANCE attribute)
        # pins the interval and disables backoff
        return _AdaptivePoll(
            self.POLL_INTERVAL,
            backoff_after=self.POLL_BACKOFF_AFTER,
            maximum=self.POLL_BACKOFF_MAX,
            adaptive="POLL_INTERVAL" not in self.__dict__,
        )

    @staticmethod
    def _make_stop_tailer(spec: ExperimentSpec, watch_path: str):
        """Early-stopping tailer over the watched metrics stream: native C++
        tailer for the default TEXT filter, Python fallback for custom
        filters / JSON (katib_tpu.native.tailer). Shared by the single-process
        and gang wait loops so their semantics can't drift."""
        from ..native.tailer import make_tailer

        mc = spec.metrics_collector_spec
        filters = (
            mc.source.filter.metrics_format if mc.source and mc.source.filter else None
        )
        return make_tailer(
            watch_path,
            spec.objective.all_metric_names(),
            filters=filters,
            json_format=bool(mc.source and mc.source.file_format == "JSON"),
        )

    @staticmethod
    def _terminate(proc: subprocess.Popen) -> None:
        try:
            os.killpg(os.getpgid(proc.pid), signal.SIGTERM)
        except (ProcessLookupError, PermissionError):
            pass
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            try:
                os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            proc.wait(timeout=5)

    @staticmethod
    def _terminate_gang(procs: Sequence[subprocess.Popen]) -> None:
        for proc in procs:
            if proc.poll() is None:
                try:
                    os.killpg(os.getpgid(proc.pid), signal.SIGTERM)
                except (ProcessLookupError, PermissionError):
                    pass
        deadline = time.time() + 10
        for proc in procs:
            try:
                proc.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
                proc.wait(timeout=5)

    CUSTOM_COLLECTOR_TIMEOUT = 60.0

    def _run_custom_collector(
        self,
        trial: Trial,
        stdout_path: str,
        metrics_file: Optional[str],
        spec: ExperimentSpec,
    ) -> None:
        mc = spec.metrics_collector_spec
        workdir = os.path.dirname(stdout_path)
        env = dict(os.environ)
        env[ENV_TRIAL_NAME] = trial.name
        env["KATIB_TRIAL_WORKDIR"] = workdir
        env["KATIB_TRIAL_STDOUT"] = stdout_path
        if metrics_file:
            env[ENV_METRICS_FILE] = metrics_file
        try:
            proc = subprocess.run(
                list(mc.custom_command),
                capture_output=True,
                text=True,
                env=env,
                cwd=workdir,
                timeout=self.CUSTOM_COLLECTOR_TIMEOUT,
            )
        except (subprocess.TimeoutExpired, OSError):
            return  # collector failure -> metrics unavailable classification
        if proc.returncode != 0:
            return
        self._parse_and_report(trial, proc.stdout.splitlines(), spec)

    def _parse_and_report(
        self, trial: Trial, lines: List[str], spec: ExperimentSpec
    ) -> None:
        """Shared metric-line parsing tail for File/StdOut/Custom collection."""
        mc = spec.metrics_collector_spec
        names = spec.objective.all_metric_names()
        filters = None
        if mc.source and mc.source.filter:
            filters = mc.source.filter.metrics_format
        base = trial.start_time or time.time()
        if mc.source and mc.source.file_format == "JSON":
            logs = parse_json_lines(lines, names, base_time=base)
        else:
            logs = parse_text_lines(lines, names, filters, base_time=base)
        if logs:
            self.obs_store.report_observation_log(trial.name, logs)

    def _drain_pushed(self, trial: Trial) -> None:
        from ..db.store import BufferedObservationStore, SqliteObservationStore

        if not self.db_path:
            return
        base = self.obs_store
        if isinstance(base, BufferedObservationStore):
            base = base.inner  # same-file check applies to the backing store
        if isinstance(base, SqliteObservationStore) and base.path == self.db_path:
            return  # same file: rows already visible (buffered reads merge)
        staging = SqliteObservationStore(self.db_path)
        try:
            rows = staging.get_observation_log(trial.name)
            if rows:
                self.obs_store.report_observation_log(trial.name, rows)
                staging.delete_observation_log(trial.name)
        finally:
            staging.close()

    def _collect(
        self,
        trial: Trial,
        stdout_path: str,
        metrics_file: Optional[str],
        spec: ExperimentSpec,
    ) -> None:
        mc = spec.metrics_collector_spec
        kind = mc.collector_kind
        if kind in (CollectorKind.NONE, CollectorKind.PUSH, CollectorKind.PROMETHEUS):
            return  # pushed directly, scraped during _wait, or reports nothing
        if kind == CollectorKind.CUSTOM and mc.custom_command:
            # user-supplied collector program (reference custom collector
            # container, common_types.go:205-227): runs after trial exit with
            # env pointing at the trial workdir; stdout parsed like File
            self._run_custom_collector(trial, stdout_path, metrics_file, spec)
            return
        if kind == CollectorKind.TF_EVENT:
            from ..runtime.tfevent import collect_tfevent_metrics

            event_dir = mc.source.file_path if mc.source else None
            if event_dir and not os.path.isabs(event_dir):
                event_dir = os.path.join(os.path.dirname(stdout_path), event_dir)
            if event_dir and os.path.isdir(event_dir):
                logs = collect_tfevent_metrics(event_dir, spec.objective.all_metric_names())
                if logs:
                    self.obs_store.report_observation_log(trial.name, logs)
            return
        path = stdout_path
        if kind == CollectorKind.FILE and metrics_file:
            path = metrics_file
        if not os.path.exists(path):
            return
        with open(path, "r", errors="replace") as f:
            lines = f.read().splitlines()
        self._parse_and_report(trial, lines, spec)


class MultiHostExecutor(SubprocessExecutor):
    """Gang executor: ``resources.num_hosts`` worker processes forming one
    jax.distributed system (SURVEY.md §7 layer 4 / hard part 5 — a worker
    death must fail the whole trial deterministically).

    TPU-native replacement for the reference's delegation to gang-scheduled
    training-operator CRDs (MPIJob/PyTorchJob,
    examples/v1beta1/kubeflow-training-operator/mpijob-horovod.yaml): the
    executor launches every worker itself, wiring the jax.distributed env
    (``KATIB_TPU_COORDINATOR/_NUM_PROCESSES/_PROCESS_ID``, read by
    ``parallel.mesh.initialize_distributed``). Command templates run the
    rendered argv per worker (the command calls ``initialize_distributed``
    like a PyTorchJob image calls ``init_process_group``); entryPoint
    templates run ``python -m katib_tpu.runtime.host_worker``.

    Process 0 is the primary (reference PrimaryPodLabels): metrics collection,
    the early-stopping tail, and the push env binding apply to its stdout.
    Any worker exiting non-zero kills the remaining gang and fails the trial
    with the worker id + exit code. Workers default to one machine (TPU-VM
    host emulation); a cluster launcher overrides ``KATIB_TPU_COORDINATOR``
    via template env when workers span machines.
    """

    @staticmethod
    def _chip_refusal(env: Dict[str, str], n_hosts: int) -> Optional[str]:
        """Why this gang cannot have the chip, or None. A chip belongs to one
        process: said here, at once, instead of workers that wait for each
        other in jax.distributed.initialize or come up on the CPU unseen."""
        from ..utils.backend import holds_accelerator
        from ..utils.compilation import _accelerator_platform

        platforms = env.get("JAX_PLATFORMS", "").lower()
        if not _accelerator_platform(platforms, environ=env):
            return None  # CPU-held workers share nothing with anyone
        if holds_accelerator():
            return (
                "this controller process has initialized the accelerator "
                "backend and owns the chip, so the gang's worker processes "
                "cannot have it; run multi-host experiments from a controller "
                "that runs no in-process JAX trials (see README, 'Who owns "
                "the chip')"
            )
        if n_hosts > 1 and "KATIB_TPU_COORDINATOR" not in env:
            return (
                f"numHosts={n_hosts} starts {n_hosts} worker processes on this "
                "one machine and each would need the host's chips; a chip "
                "belongs to one process and per-worker chip partitioning does "
                "not exist. Use numHosts 1 with numDevices N (one in-process "
                "trial over N chips), pin KATIB_TPU_COORDINATOR in the "
                "template env when a launcher places the workers on separate "
                "machines, or set JAX_PLATFORMS=cpu in the template env"
            )
        return None

    def execute(
        self, exp: Experiment, trial: Trial, ctx: TrialContext, handle: TrialExecution
    ) -> ExecutionResult:
        import json as _json
        import sys as _sys

        spec = exp.spec
        template = spec.trial_template
        n_hosts = max(template.resources.num_hosts, 1)
        workdir = ctx.workdir or os.getcwd()
        os.makedirs(workdir, exist_ok=True)

        if template.command is not None:
            cmd = render_command(template, trial)
        else:
            cmd = [_sys.executable, "-m", "katib_tpu.runtime.host_worker"]

        base_env = dict(os.environ)
        base_env.update(template.env)
        refusal = self._chip_refusal(base_env, n_hosts)
        if refusal is not None:
            return ExecutionResult(TrialOutcome.FAILED, refusal)
        # workers must import katib_tpu regardless of their cwd
        repo_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        base_env["PYTHONPATH"] = (
            repo_root + os.pathsep + base_env.get("PYTHONPATH", "")
        ).rstrip(os.pathsep)
        base_env[ENV_TRIAL_NAME] = trial.name
        base_env["KATIB_TPU_EXPERIMENT"] = trial.experiment_name
        self._stamp_profile_env(base_env)
        if ctx.trace_id and ctx.trace_parent:
            from ..tracing import ENV_TRACEPARENT, format_traceparent

            base_env[ENV_TRACEPARENT] = format_traceparent(
                ctx.trace_id, ctx.trace_parent
            )
        # coordinator endpoint: auto-assigned unless the template/env pins it
        # (a cluster launcher spanning machines). Auto ports come from a
        # probe-close-bind cycle, so an unrelated process can steal the port
        # in the window — detected below and retried with a fresh port.
        auto_port = "KATIB_TPU_COORDINATOR" not in base_env
        base_env["KATIB_TPU_NUM_PROCESSES"] = str(n_hosts)
        if template.entry_point is not None:
            base_env["KATIB_TPU_ENTRY_POINT"] = template.entry_point
            base_env["KATIB_TPU_ASSIGNMENTS"] = _json.dumps(trial.assignments_dict())
        if ctx.checkpoint_dir:
            base_env["KATIB_TPU_CHECKPOINT_DIR"] = ctx.checkpoint_dir
        if template.resources.topology:
            base_env["KATIB_TPU_TOPOLOGY"] = template.resources.topology

        metrics_file = None
        mc = spec.metrics_collector_spec
        if mc.collector_kind == CollectorKind.FILE and mc.source and mc.source.file_path:
            metrics_file = mc.source.file_path
            if not os.path.isabs(metrics_file):
                # every worker's cwd is its per-host dir (or the shared
                # working_dir override), so a script writing the relative
                # filePath from its cwd lands in host-0/<file> for the
                # primary — watch there, not the trial workdir, or the
                # collector reports no metrics (single-host runs with
                # cwd=workdir and is unaffected)
                base = template.working_dir or os.path.join(workdir, "host-0")
                metrics_file = os.path.join(base, metrics_file)

        monitor = None
        if trial.early_stopping_rules:
            monitor = EarlyStoppingMonitor(
                trial.early_stopping_rules,
                spec.objective.objective_metric_name,
                spec.objective.type,
            )

        stdout0 = os.path.join(workdir, "host-0", "stdout.log")
        for attempt in range(2):
            if auto_port:
                base_env["KATIB_TPU_COORDINATOR"] = f"127.0.0.1:{_free_port()}"
            procs: List[subprocess.Popen] = []
            outs = []
            prom_logs: List[MetricLog] = []
            try:
                for i in range(n_hosts):
                    hostdir = os.path.join(workdir, f"host-{i}")
                    os.makedirs(hostdir, exist_ok=True)
                    env_i = dict(base_env)
                    env_i["KATIB_TPU_PROCESS_ID"] = str(i)
                    env_i["KATIB_TPU_WORKDIR"] = hostdir
                    if i == 0:
                        # primary: push binding + metrics file land here only,
                        # so N workers never produce N duplicate observations
                        if self.db_path:
                            env_i[ENV_DB_PATH] = self.db_path
                        if metrics_file:
                            env_i[ENV_METRICS_FILE] = metrics_file
                    out = open(os.path.join(hostdir, "stdout.log"), "wb")
                    outs.append(out)
                    procs.append(
                        subprocess.Popen(
                            cmd,
                            stdout=out,
                            stderr=subprocess.STDOUT,
                            env=env_i,
                            cwd=template.working_dir or hostdir,
                            start_new_session=True,
                        )
                    )
                if ctx.on_subprocess is not None:
                    # telemetry samples the WHOLE gang: RSS is summed across
                    # the worker processes, vanished pids are skipped
                    ctx.on_subprocess([p.pid for p in procs])
                outcome = self._wait_gang(
                    procs, stdout0, metrics_file, monitor, spec, handle, prom_logs,
                    heartbeat=ctx.on_report,
                )
            except BaseException:
                # spawn or wait blew up: never orphan already-started workers
                # (they would block in jax.distributed.initialize forever)
                self._terminate_gang(procs)
                raise
            finally:
                for out in outs:
                    out.close()
            if (
                attempt == 0
                and auto_port
                and outcome is not None
                and outcome.outcome == TrialOutcome.FAILED
                and self._port_collision(workdir, base_env["KATIB_TPU_COORDINATOR"])
            ):
                # an unrelated process bound our probed port between the
                # probe close and the coordinator bind — not the trial's
                # fault; relaunch the whole gang once on a fresh port
                # (worker stdout logs are truncated by the reopen above)
                log.warning(
                    "gang coordinator port was taken (TOCTOU); relaunching "
                    "trial %s with a fresh port", trial.name,
                )
                continue
            break

        if prom_logs:
            self.obs_store.report_observation_log(trial.name, prom_logs)
        self._collect(trial, stdout0, metrics_file, spec)
        self._drain_pushed(trial)

        rc0 = procs[0].returncode if procs else None
        if outcome is not None:
            if outcome.exit_code is None:
                # keep the failing worker's code (set by _wait_gang) — the
                # SIGTERM'd primary's -15 would shadow it for conditions
                outcome.exit_code = rc0
            outcome.stdout_path = stdout0
            return outcome
        return ExecutionResult(
            TrialOutcome.COMPLETED, exit_code=rc0, stdout_path=stdout0
        )

    PORT_COLLISION_MARKERS = (
        b"Address already in use",
        b"EADDRINUSE",
        b"Failed to bind",
        b"address in use",
    )

    def _port_collision(self, workdir: str, coordinator: str) -> bool:
        """Did the gang die on a COORDINATOR bind failure? (the TOCTOU
        window between the _free_port probe closing and the jax.distributed
        coordinator binding). Only host-0 binds the coordinator, and its
        error names the endpoint — both are required, so a workload's own
        unrelated bind failure (e.g. a metrics server on a busy fixed port)
        is not misclassified and retried."""
        port = coordinator.rsplit(":", 1)[-1].encode()
        path = os.path.join(workdir, "host-0", "stdout.log")
        try:
            with open(path, "rb") as f:
                f.seek(0, 2)
                f.seek(max(0, f.tell() - 8192))
                tail = f.read()
        except OSError:
            return False
        return port in tail and any(m in tail for m in self.PORT_COLLISION_MARKERS)

    def _wait_gang(
        self,
        procs: List[subprocess.Popen],
        stdout_path: str,
        metrics_file: Optional[str],
        monitor: Optional[EarlyStoppingMonitor],
        spec: ExperimentSpec,
        handle: TrialExecution,
        prom_logs: List[MetricLog],
        heartbeat: Optional[Callable[[], None]] = None,
    ) -> Optional[ExecutionResult]:
        """Poll the gang; returns None only when EVERY worker exited 0.
        Same adaptive backoff (and telemetry heartbeat contract) as the
        single-process wait loop."""
        watch_path = metrics_file or stdout_path
        scrape = (
            spec.metrics_collector_spec.collector_kind == CollectorKind.PROMETHEUS
            and spec.metrics_collector_spec.source is not None
        )
        last_scrape = 0.0
        last_scraped: Dict[str, Any] = {}
        tailer = self._make_stop_tailer(spec, watch_path) if monitor else None
        poll = self._make_poll()
        try:
            while True:
                if handle.kill_requested:
                    self._terminate_gang(procs)
                    return ExecutionResult(TrialOutcome.KILLED, "kill requested")
                rcs = [p.poll() for p in procs]
                # deterministic gang failure: first worker death kills the rest
                for i, rc in enumerate(rcs):
                    if rc is not None and rc != 0:
                        from ..telemetry import oom_kill_suspected

                        self._terminate_gang(procs)
                        msg = (
                            f"worker {i}/{len(procs)} exited with code {rc}; "
                            "gang killed"
                        )
                        if oom_kill_suspected(rc):
                            msg += (
                                " (SIGKILL death — likely OOM-killed by the "
                                "kernel; see the trial's telemetry for the "
                                "RSS ramp)"
                            )
                        return ExecutionResult(
                            TrialOutcome.FAILED,
                            msg,
                            exit_code=rc,  # the FAILING worker's code
                        )
                if scrape and time.time() - last_scrape >= self.SCRAPE_INTERVAL:
                    last_scrape = time.time()
                    before = len(prom_logs)
                    stopped = self._scrape_prometheus(spec, prom_logs, monitor, last_scraped)
                    if len(prom_logs) > before:
                        poll.activity()
                        if heartbeat is not None:
                            heartbeat()
                    if stopped is not None:
                        self._terminate_gang(procs)
                        return stopped
                if tailer is not None:
                    parsed = tailer.poll()
                    if parsed:
                        poll.activity()
                        if heartbeat is not None:
                            heartbeat()
                    for name, raw, _idx in parsed:
                        try:
                            value = float(raw)
                        except ValueError:
                            continue
                        if monitor.observe(name, value):
                            self._terminate_gang(procs)
                            return ExecutionResult(TrialOutcome.EARLY_STOPPED)
                if all(rc == 0 for rc in rcs):
                    if scrape:
                        self._scrape_prometheus(spec, prom_logs, monitor, last_scraped)
                    return None
                time.sleep(poll.next_delay())
        finally:
            if tailer is not None:
                tailer.close()
