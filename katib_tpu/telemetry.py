"""Per-trial resource telemetry — RSS/CPU/HBM sampling + health watchdog.

PR 4's tracing answers *where a trial's wall-clock goes*; this module
answers *what a trial costs while it runs*. SURVEY.md §5 names
resource-level observability as the TPU-native capability the reference
(logs + Prometheus counters) never had, and Podracer-style fleets
(arXiv:2104.06272) tune packed/preempted schedulers like ours (PR 1/2)
off exactly this data: unobserved memory headroom and silent stalls are
where accelerator-hours go to die.

:class:`ResourceSampler` is a controller-side daemon thread that, every
``runtime.telemetry_interval_seconds`` (default 5 s), samples

- per-device accelerator memory via ``jax.local_devices()[i].memory_stats()``
  — guarded: CPU backends return None, and JAX is only consulted when the
  process already imported it (a read-only CLI must not pay the JAX import);
- host RSS / CPU per running trial: in-process trials are attributed the
  controller process's ``/proc/self`` numbers (shared attribution — flagged
  ``inProcess`` in every sample), subprocess/multi-host trials are read from
  ``/proc/<pid>`` of the children the executor registered;
- XLA persistent-compile-cache size and entry count (the
  ``utils/compilation.py`` directory).

Samples land in bounded per-trial rings persisted under
``<root>/telemetry/<experiment>/<trial>.json`` (same layout as
``<root>/traces/``), feed the MetricsRegistry
(``katib_trial_host_rss_bytes{trial=}``, ``katib_trial_cpu_percent``,
``katib_device_hbm_used_bytes{device=}``, ``katib_xla_cache_entries``,
``katib_telemetry_samples_total``) through the registry's collector hook,
and produce a peak-RSS / peak-HBM / mean-CPU summary that the scheduler
stamps onto the PR 4 trial root span at finalize.

On top of the sampler sits the **health watchdog**:

- a trial with no ``ctx.report()`` heartbeat for ``runtime.stall_seconds``
  emits a ``TrialStalled`` warning event + ``katib_trial_stalled_total``
  (once per run stint; a later heartbeat re-arms it);
- monotonic RSS growth crossing ``runtime.oom_risk_fraction`` of host
  memory emits ``TrialOOMRisk`` *before* the kernel's OOM killer fires;
- subprocess exits with rc=-9 are classified by :func:`oom_kill_suspected`
  and surfaced as a likely OOM-kill in the trial's terminal status
  (controller/executor.py).

Disabled (``runtime.telemetry=false`` / ``KATIB_TPU_TELEMETRY=0``) every
call site reduces to one boolean check: ``heartbeat``/``register_trial``/
``unregister_trial`` return immediately and no thread is started.
"""

from __future__ import annotations

import collections
import functools
import json
import logging
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Deque, Dict, List, Optional, Sequence

log = logging.getLogger("katib_tpu.telemetry")

ENV_TELEMETRY = "KATIB_TPU_TELEMETRY"

SAMPLES_TOTAL_METRIC = "katib_telemetry_samples_total"
STALLED_TOTAL_METRIC = "katib_trial_stalled_total"
OOM_RISK_TOTAL_METRIC = "katib_trial_oom_risk_total"
TRIAL_RSS_METRIC = "katib_trial_host_rss_bytes"
TRIAL_CPU_METRIC = "katib_trial_cpu_percent"
DEVICE_HBM_METRIC = "katib_device_hbm_used_bytes"
XLA_CACHE_ENTRIES_METRIC = "katib_xla_cache_entries"
XLA_CACHE_BYTES_METRIC = "katib_xla_cache_bytes"

# gauge families the sampler's collector owns: series for finished trials
# (or removed devices) vanish from /metrics on the next scrape
COLLECTOR_GAUGES = (
    TRIAL_RSS_METRIC,
    TRIAL_CPU_METRIC,
    DEVICE_HBM_METRIC,
    XLA_CACHE_ENTRIES_METRIC,
    XLA_CACHE_BYTES_METRIC,
)


def telemetry_enabled_from_env(default: bool = True) -> bool:
    raw = os.environ.get(ENV_TELEMETRY)
    if raw is None or raw == "":
        return default
    return raw.lower() not in ("0", "false", "off")


def oom_kill_suspected(returncode: Optional[int]) -> bool:
    """Was this subprocess exit the kernel's SIGKILL? Popen reports a signal
    death as -signum (-9); shell-wrapped commands surface it as 128+9."""
    return returncode in (-9, 137)


OOM_KILL_MESSAGE = (
    "process killed by SIGKILL (rc=-9) — likely OOM-killed by the kernel; "
    "see the trial's telemetry (katib_trial_host_rss_bytes / "
    "/api/experiments/<e>/trials/<t>/telemetry) for the RSS ramp"
)


# -- /proc readers -----------------------------------------------------------

def read_host_memory_total() -> Optional[int]:
    """MemTotal from /proc/meminfo, bytes; None off-Linux."""
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        pass
    return None


def read_rss_bytes(pid: int) -> Optional[int]:
    """Resident set size of one process from /proc/<pid>/statm (field 2,
    pages); None for a vanished pid."""
    try:
        with open(f"/proc/{pid}/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        return None


def read_cpu_seconds(pid: int) -> Optional[float]:
    """utime+stime of one process in seconds from /proc/<pid>/stat. The
    comm field may contain spaces/parens, so fields are taken after the
    LAST ')' (utime/stime are fields 14/15 of the full line)."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            raw = f.read()
        rest = raw.rsplit(")", 1)[1].split()
        # rest[0] is field 3 (state); utime is field 14 -> rest[11]
        ticks = int(rest[11]) + int(rest[12])
        return ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


def scan_xla_cache(directory: Optional[str]) -> Dict[str, int]:
    """Entry count + total bytes of the persistent XLA compile cache dir
    (utils/compilation.py). Files may vanish mid-scan (another process's
    cache eviction) — skipped, same contract as list_profile_artifacts."""
    out = {"entries": 0, "bytes": 0}
    if not directory or not os.path.isdir(directory):
        return out
    for dirpath, dirnames, filenames in os.walk(directory):
        dirnames.sort()
        for fn in sorted(filenames):
            try:
                out["bytes"] += os.path.getsize(os.path.join(dirpath, fn))
            except OSError:
                continue  # vanished between listdir and stat
            out["entries"] += 1
    return out


def read_device_memory(events=None) -> List[Dict[str, Any]]:
    """Per-device accelerator memory from ``memory_stats()`` — ONLY when a
    backend is already up in this process. The sampler thread never
    initializes one: the process that does owns the chip, and a controller
    whose trials run as subprocesses must leave it to them. Tolerant of CPU
    backends whose ``memory_stats`` is None/absent/empty."""
    from .utils.backend import initialized_local_devices

    out: List[Dict[str, Any]] = []
    devices = initialized_local_devices()
    if devices is None:
        return []
    for d in devices:
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        out.append(
            {
                "device": str(getattr(d, "id", len(out))),
                "kind": getattr(d, "device_kind", "?"),
                "bytesInUse": int(stats.get("bytes_in_use", 0)),
                "peakBytesInUse": int(
                    stats.get("peak_bytes_in_use", stats.get("bytes_in_use", 0))
                ),
                "bytesLimit": int(stats.get("bytes_limit", 0)) or None,
            }
        )
    return out


# -- per-trial tracking ------------------------------------------------------

@dataclass
class _Track:
    """Book-keeping for one running trial stint."""

    experiment: str
    trial: str
    pids: Optional[List[int]]  # None = in-process (controller's own /proc)
    registered_at: float
    samples: Deque[Dict[str, Any]]
    last_heartbeat: Optional[float] = None
    # cpu% needs a previous observation: cpu-seconds + wall per pid-set
    prev_cpu: Optional[float] = None
    prev_wall: Optional[float] = None
    # summary accumulators (stamped onto the trial root span at finalize)
    peak_rss: int = 0
    peak_hbm: int = 0
    cpu_sum: float = 0.0
    cpu_n: int = 0
    # watchdog state — one warning per condition per stint
    stall_emitted: bool = False
    oom_emitted: bool = False
    rss_trail: List[int] = field(default_factory=list)  # recent RSS readings


class ResourceSampler:
    """Bounded, thread-safe per-trial resource sampler + health watchdog.

    One ring (deque) of samples per running trial bounds memory; finished
    trials' rings are persisted as one small JSON file each under
    ``persist_dir`` so ``katib-tpu top`` and the trial telemetry endpoint
    work after the controller exits.
    """

    RSS_TRAIL = 3  # consecutive growths required before TrialOOMRisk

    def __init__(
        self,
        enabled: bool = True,
        interval: float = 5.0,
        metrics=None,
        events=None,
        persist_dir: Optional[str] = None,
        stall_seconds: float = 120.0,
        oom_risk_fraction: float = 0.9,
        ring_size: int = 720,
        host_memory_bytes: Optional[int] = None,
    ):
        self.enabled = enabled
        self.interval = interval
        self.metrics = metrics
        self.events = events
        self.persist_dir = persist_dir
        self.stall_seconds = stall_seconds
        self.oom_risk_fraction = oom_risk_fraction
        self.ring_size = ring_size
        self.host_memory_bytes = (
            host_memory_bytes
            if host_memory_bytes is not None
            else read_host_memory_total()
        )
        self._lock = threading.Lock()
        self._tracks: Dict[str, _Track] = {}
        self._devices: List[Dict[str, Any]] = []
        self._xla_cache: Dict[str, int] = {"entries": 0, "bytes": 0}
        self._last_sample_at: Optional[float] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # overridable readers (tests inject synthetic RSS/CPU ramps); the
        # device reader carries the recorder so a wedged backend init
        # surfaces as one BackendInitFailed event instead of a hung tick
        self._read_rss = read_rss_bytes
        self._read_cpu = read_cpu_seconds
        self._read_devices = functools.partial(read_device_memory, events=events)
        if enabled and metrics is not None:
            metrics.add_collector(self._collect_gauges, names=COLLECTOR_GAUGES)

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> None:
        """Spawn the daemon sampling thread (idempotent; no-op disabled)."""
        if not self.enabled or self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="katib-telemetry"
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=2.0)
            self._thread = None

    def _loop(self) -> None:
        while not self._stop.wait(self.interval):
            try:
                self.sample_once()
            except Exception:
                # the sampler must never take the controller down; a
                # persistent bug shows up in the log, not as lost trials
                log.warning("telemetry sample failed", exc_info=True)

    # -- registration + heartbeats (the per-report hot path) -----------------

    def register_trial(
        self, experiment: str, trial: str, pids: Optional[Sequence[int]] = None
    ) -> None:
        if not self.enabled:
            return
        now = time.time()
        with self._lock:
            self._tracks[trial] = _Track(
                experiment=experiment,
                trial=trial,
                pids=list(pids) if pids else None,
                registered_at=now,
                samples=collections.deque(maxlen=self.ring_size),
            )

    def set_pids(self, trial: str, pids: Sequence[int]) -> None:
        """Executor hook: the trial's subprocess children exist now."""
        if not self.enabled:
            return
        with self._lock:
            track = self._tracks.get(trial)
            if track is not None:
                track.pids = list(pids)
                track.prev_cpu = track.prev_wall = None

    def heartbeat(self, trial: str) -> None:
        """ctx.report() liveness hook — one dict lookup + float store; the
        watchdog's stall clock resets here (and re-arms the warning)."""
        if not self.enabled:
            return
        track = self._tracks.get(trial)  # racy read is fine: floats are atomic
        if track is not None:
            track.last_heartbeat = time.time()
            track.stall_emitted = False

    def unregister_trial(self, trial: str) -> Optional[Dict[str, Any]]:
        """Drop the trial's track, persist its ring, and return the summary
        the scheduler stamps onto the trial's root span:
        ``{peakRssBytes, peakHbmBytes, meanCpuPercent, samples}``."""
        if not self.enabled:
            return None
        with self._lock:
            track = self._tracks.pop(trial, None)
        if track is None:
            return None
        summary = self._summary(track)
        self._persist(track, summary)
        return summary

    @staticmethod
    def _summary(track: _Track) -> Dict[str, Any]:
        return {
            "peakRssBytes": track.peak_rss or None,
            "peakHbmBytes": track.peak_hbm or None,
            "meanCpuPercent": (
                round(track.cpu_sum / track.cpu_n, 2) if track.cpu_n else None
            ),
            "samples": len(track.samples),
        }

    # -- the sampling tick ---------------------------------------------------

    def sample_once(self, now: Optional[float] = None) -> int:
        """One sampling pass over devices, the XLA cache, and every tracked
        trial; runs the watchdog. Returns the number of per-trial samples
        recorded (the loop calls this; tests call it directly)."""
        if not self.enabled:
            return 0
        now = time.time() if now is None else now
        devices = self._read_devices()
        from .utils.compilation import cache_dir

        cache = scan_xla_cache(cache_dir())
        device_peak = max((d["bytesInUse"] for d in devices), default=0)
        with self._lock:
            tracks = list(self._tracks.values())
            self._devices = devices
            self._xla_cache = cache
            self._last_sample_at = now
        # /proc/self is read once per tick and shared by every in-process
        # trial (they live in THIS process; per-thread RSS does not exist)
        self_rss = self_cpu = None
        if any(t.pids is None for t in tracks):
            self_pid = os.getpid()
            self_rss = self._read_rss(self_pid)
            self_cpu = self._read_cpu(self_pid)
        n_samples = 0
        for track in tracks:
            in_process = track.pids is None
            if in_process:
                rss, cpu_s = self_rss, self_cpu
            else:
                rss_vals = [self._read_rss(p) for p in track.pids]
                cpu_vals = [self._read_cpu(p) for p in track.pids]
                rss_vals = [v for v in rss_vals if v is not None]
                cpu_vals = [v for v in cpu_vals if v is not None]
                rss = sum(rss_vals) if rss_vals else None
                cpu_s = sum(cpu_vals) if cpu_vals else None
            cpu_pct = None
            if cpu_s is not None:
                if track.prev_cpu is not None and now > track.prev_wall:
                    cpu_pct = max(
                        100.0 * (cpu_s - track.prev_cpu) / (now - track.prev_wall),
                        0.0,
                    )
                track.prev_cpu, track.prev_wall = cpu_s, now
            sample = {
                "timestamp": round(now, 3),
                "rssBytes": rss,
                "cpuPercent": round(cpu_pct, 2) if cpu_pct is not None else None,
                "hbmBytes": device_peak or None,
                "heartbeatAgeSeconds": round(
                    now - (track.last_heartbeat or track.registered_at), 3
                ),
                "inProcess": in_process,
            }
            track.samples.append(sample)
            n_samples += 1
            if rss is not None:
                track.peak_rss = max(track.peak_rss, rss)
                track.rss_trail.append(rss)
                del track.rss_trail[: -self.RSS_TRAIL - 1]
            track.peak_hbm = max(track.peak_hbm, device_peak)
            if cpu_pct is not None:
                track.cpu_sum += cpu_pct
                track.cpu_n += 1
            self._watchdog(track, now, rss)
        if self.metrics is not None and n_samples:
            self.metrics.inc(SAMPLES_TOTAL_METRIC, value=float(n_samples))
        return n_samples

    # -- health watchdog -----------------------------------------------------

    def _watchdog(self, track: _Track, now: float, rss: Optional[int]) -> None:
        # stall: no report() heartbeat for stall_seconds (a trial that never
        # reported at all is measured from registration — compile stretches
        # longer than the threshold surface too, by design: the operator
        # tunes runtime.stall_seconds above the expected compile time)
        base = track.last_heartbeat or track.registered_at
        if (
            self.stall_seconds
            and not track.stall_emitted
            and now - base > self.stall_seconds
        ):
            track.stall_emitted = True
            age = now - base
            log.warning(
                "trial %s has had no metric report for %.3gs "
                "(threshold %.3gs) — stalled, wedged backend, or a very "
                "long compile", track.trial, age, self.stall_seconds,
            )
            if self.metrics is not None:
                self.metrics.inc(STALLED_TOTAL_METRIC, experiment=track.experiment)
            if self.events is not None:
                self.events.event(
                    track.experiment, "Trial", track.trial, "TrialStalled",
                    f"no metric report for {age:.3g}s (stall threshold "
                    f"{self.stall_seconds:.3g}s); the trial may be wedged — "
                    "see its telemetry time series",
                    warning=True,
                )
        # OOM risk: monotonic RSS growth over the recent trail AND past the
        # configured fraction of host memory — warn BEFORE the kernel kills
        if (
            rss is not None
            and not track.oom_emitted
            and self.host_memory_bytes
            and self.oom_risk_fraction
            and rss > self.oom_risk_fraction * self.host_memory_bytes
            and len(track.rss_trail) > self.RSS_TRAIL
            and all(
                a < b
                for a, b in zip(track.rss_trail[-self.RSS_TRAIL - 1:],
                                track.rss_trail[-self.RSS_TRAIL:])
            )
        ):
            track.oom_emitted = True
            pct = 100.0 * rss / self.host_memory_bytes
            log.warning(
                "trial %s RSS %.0f MiB is %.0f%% of host memory and still "
                "growing — OOM-kill risk", track.trial, rss / 2**20, pct,
            )
            if self.metrics is not None:
                self.metrics.inc(OOM_RISK_TOTAL_METRIC, experiment=track.experiment)
            if self.events is not None:
                self.events.event(
                    track.experiment, "Trial", track.trial, "TrialOOMRisk",
                    f"RSS {rss / 2**20:.0f} MiB is {pct:.0f}% of host memory "
                    "and growing monotonically; the kernel OOM killer fires "
                    "next — checkpoint or shrink the trial",
                    warning=True,
                )

    # -- metrics collector ---------------------------------------------------

    def _collect_gauges(self) -> Dict:
        """Registry collector hook (the reference's custom-collector
        pattern): current-state telemetry gauges recomputed per scrape from
        the latest sample, so finished trials' series vanish."""
        if self.metrics is None:
            return {}
        key = self.metrics.gauge_key
        gauges: Dict = {}
        with self._lock:
            tracks = list(self._tracks.values())
            devices = list(self._devices)
            cache = dict(self._xla_cache)
        for track in tracks:
            latest = track.samples[-1] if track.samples else None
            if latest is None:
                continue
            if latest["rssBytes"] is not None:
                gauges[
                    key(TRIAL_RSS_METRIC, experiment=track.experiment, trial=track.trial)
                ] = float(latest["rssBytes"])
            if latest["cpuPercent"] is not None:
                gauges[
                    key(TRIAL_CPU_METRIC, experiment=track.experiment, trial=track.trial)
                ] = float(latest["cpuPercent"])
        for d in devices:
            gauges[key(DEVICE_HBM_METRIC, device=d["device"])] = float(d["bytesInUse"])
        gauges[key(XLA_CACHE_ENTRIES_METRIC)] = float(cache.get("entries", 0))
        gauges[key(XLA_CACHE_BYTES_METRIC)] = float(cache.get("bytes", 0))
        return gauges

    # -- export --------------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        """Cluster-wide current state for ``GET /api/telemetry`` and the
        ``katib-tpu top`` table."""
        with self._lock:
            tracks = list(self._tracks.values())
            devices = list(self._devices)
            cache = dict(self._xla_cache)
            last = self._last_sample_at
        trials = []
        for track in sorted(tracks, key=lambda t: (t.experiment, t.trial)):
            latest = track.samples[-1] if track.samples else {}
            trials.append(
                {
                    "experiment": track.experiment,
                    "trial": track.trial,
                    "rssBytes": latest.get("rssBytes"),
                    "cpuPercent": latest.get("cpuPercent"),
                    "hbmBytes": latest.get("hbmBytes"),
                    "heartbeatAgeSeconds": latest.get("heartbeatAgeSeconds"),
                    "inProcess": track.pids is None,
                    "stalled": track.stall_emitted,
                    "oomRisk": track.oom_emitted,
                    **{k: v for k, v in self._summary(track).items() if k != "samples"},
                    "samples": len(track.samples),
                }
            )
        return {
            "enabled": self.enabled,
            "intervalSeconds": self.interval,
            "lastSampleAt": last,
            "hostMemoryTotalBytes": self.host_memory_bytes,
            "devices": devices,
            "xlaCache": cache,
            "trials": trials,
        }

    def trial_series(self, experiment: str, trial: str) -> Optional[Dict[str, Any]]:
        """One trial's telemetry time series: the live ring while it runs,
        the persisted file afterwards; None when unknown."""
        with self._lock:
            track = self._tracks.get(trial)
            if track is not None and track.experiment == experiment:
                return {
                    "experiment": experiment,
                    "trial": trial,
                    "live": True,
                    "summary": self._summary(track),
                    "samples": list(track.samples),
                }
        return self._load_persisted(experiment, trial)

    # -- persistence (same path hygiene as tracing.Tracer) -------------------

    def _series_path(self, experiment: str, trial: str) -> Optional[str]:
        if not self.persist_dir:
            return None
        bad = any(
            "/" in n or "\\" in n or ".." in n or "\x00" in n or not n
            for n in (experiment, trial)
        )
        if bad:
            return None
        return os.path.join(self.persist_dir, experiment, f"{trial}.json")

    def _persist(self, track: _Track, summary: Dict[str, Any]) -> None:
        path = self._series_path(track.experiment, track.trial)
        if path is None or not track.samples:
            return
        payload = {
            "experiment": track.experiment,
            "trial": track.trial,
            "live": False,
            "summary": summary,
            "samples": list(track.samples),
        }
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(payload, f)
            os.replace(tmp, path)
        except OSError:
            log.warning(
                "failed to persist telemetry for %s/%s",
                track.experiment, track.trial, exc_info=True,
            )

    def _load_persisted(self, experiment: str, trial: str) -> Optional[Dict[str, Any]]:
        path = self._series_path(experiment, trial)
        if path is None or not os.path.exists(path):
            return None
        try:
            with open(path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None


# -- rendering helpers (katib-tpu top) ---------------------------------------

def fmt_bytes(n: Optional[float]) -> str:
    if n is None:
        return "-"
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if n < 1024 or unit == "TiB":
            return f"{n:.0f}{unit}" if unit == "B" else f"{n:.1f}{unit}"
        n /= 1024.0
    return f"{n:.1f}TiB"


def top_rows(snapshot: Dict[str, Any]) -> List[tuple]:
    """``katib-tpu top`` table rows from a /api/telemetry-shaped snapshot
    (live or reconstructed from persisted files)."""
    rows = []
    for t in snapshot.get("trials", []):
        age = t.get("heartbeatAgeSeconds")
        flags = []
        if t.get("stalled"):
            flags.append("STALLED")
        if t.get("oomRisk"):
            flags.append("OOM-RISK")
        rows.append(
            (
                t.get("trial", "?"),
                t.get("experiment", "?"),
                fmt_bytes(t.get("rssBytes")),
                "-" if t.get("cpuPercent") is None else f"{t['cpuPercent']:.0f}%",
                fmt_bytes(t.get("hbmBytes")),
                "-" if age is None else f"{age:.0f}s",
                ",".join(flags) or ("live" if t.get("live", True) else "done"),
            )
        )
    return rows


def snapshot_from_persisted(persist_dir: str) -> Dict[str, Any]:
    """Offline ``katib-tpu top``: rebuild a snapshot-shaped view from the
    persisted per-trial series under ``<root>/telemetry/`` (last sample +
    summary per trial), so resource history outlives the controller."""
    trials = []
    if os.path.isdir(persist_dir):
        for experiment in sorted(os.listdir(persist_dir)):
            exp_dir = os.path.join(persist_dir, experiment)
            if not os.path.isdir(exp_dir):
                continue
            for fn in sorted(os.listdir(exp_dir)):
                if not fn.endswith(".json"):
                    continue
                try:
                    with open(os.path.join(exp_dir, fn)) as f:
                        series = json.load(f)
                except (OSError, ValueError):
                    continue
                samples = series.get("samples") or []
                latest = samples[-1] if samples else {}
                summary = series.get("summary") or {}
                trials.append(
                    {
                        "experiment": series.get("experiment", experiment),
                        "trial": series.get("trial", fn[:-5]),
                        "rssBytes": latest.get("rssBytes"),
                        "cpuPercent": latest.get("cpuPercent"),
                        "hbmBytes": latest.get("hbmBytes"),
                        "heartbeatAgeSeconds": latest.get("heartbeatAgeSeconds"),
                        "live": False,
                        "peakRssBytes": summary.get("peakRssBytes"),
                        "peakHbmBytes": summary.get("peakHbmBytes"),
                        "meanCpuPercent": summary.get("meanCpuPercent"),
                        "samples": len(samples),
                    }
                )
    return {"enabled": True, "live": False, "trials": trials}
